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
import operator

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
# in place ("Ragged Paged Attention", PAPERS.md), and the KERNEL walks it,
# not the grid: the grid is (row, query tile), one program each; the pools
# stay in HBM, the table and the offsets are scalar-prefetched, and a
# program works out from them the logical pages its queries can attend —
# up to its last query's page, from 0 or from the window's first page —,
# loops over them in groups of ``G`` and copies each group's K and V pages
# out of the pool itself into one of two VMEM slots, group g + 1 started
# before group g is waited for (``_page_walk``, the one walk of this file's
# three kernels) — an aligned stretch of ``PAGE_RUN`` table entries that name
# neighbours in the pool as ONE copy a pool (``_by_runs``: the server's
# allocator hands a slot its pages in such blocks), and a program's last
# step starting the first group of the program after it. A table column
# past a row's context costs nothing: no grid step, no copy, no skipped
# branch. (A grid over every
# column cost 0.14-0.24 us a column a lane on a v5e, 110 ns where the math
# was skipped, with a third of the columns live: PERF.md, PR 41.)
#
# A head NARROWER than 128 lanes has pools of another shape, and a kernel of
# its own below (``_narrow_kernel``). Mosaic sees a [.., kv_heads, dh < 128]
# pool padded to whole 128-lane rows and takes a copy out of it only in
# whole rows ("Slice shape along dimension 2 must be aligned to tiling
# (128)": of a VMEM slot padded to 128 lanes and of ``pltpu.emit_pipeline``'s
# copies too), and XLA keeps such a pool with its pages minor and re-lays it
# around every step. So such a model's pools are ROW-MAJOR, [layers, pages,
# page, kv_heads * dh] (``models/paged_decode.cache_spec``): a token's heads
# side by side, whole 128-lane runs (8 heads of 64: four), which a copy
# takes as they are. (Until PR 46 a grid over every column of the table, a
# page a step through a BlockSpec, read them: 3-6 x a call, PERF.md PR 41.)
#
# Layout (what the TPU tiling accepts): a copy takes ALL kv heads of a
# page. The pool rides whole and is viewed as flat rows, [layers*num_pages
# *page*kv_heads, dh] — a free bitcast of its HBM layout at a head of 128
# lanes, rows ordered (page, slot, kv head), so that neighbouring pages are
# one stretch of rows; the table is offset to the layer's pages so the
# layer loop never slices the pool. (The lane-dense view [..,
# page, kv_heads*dh] is NOT free: XLA keeps the pool tiled over (kv head,
# dh) and re-lays all of it for that view; and a bfloat16 pool packs two
# kv heads' rows into one word, so no copy can take one head's rows. A
# group's pages therefore land in VMEM as the pool holds them.)
#
# Two cuts of one algorithm, chosen by ``per_kv_head`` from the call's
# shapes. A DECODE step's tile has ``heads / kv_heads`` rows a K/V head —
# 4 to 16, under or at one float32 sublane tile — so its queries are folded
# [B, heads, dh], ONE [rows, dh] x [dh, G*page*kv_heads] product scores
# every query head against every kv head of the group's pages, and entries
# whose kv head is not the row's own are masked with the causal bound: the
# product is small beside the copies, and per-head products of 4-16 rows
# measured the same on a v5e (PERF.md, PR 42). A CHUNK's tile has a
# sublane tile's multiple of rows a K/V head, and there the masked product
# IS the call: three columns of four, or seven of eight, multiplied,
# exponentiated and reduced for nothing (3,667 us a 512-token call at
# offset 4,096 on MiMo's full layers against 1,614 a head at a time,
# PERF.md PR 43). So a chunk's queries are folded (kv head, position,
# query head of the group) by the wrapper, and each K/V head's rows are read
# out of the slot as it was copied, with a sublane stride, and multiplied by
# that head's query rows alone: [rows / kvh, dh] x [dh, G*page], nothing
# masked across heads, the online softmax a head, its sums carried by the
# walk's loop (in VMEM scratch, read and written a step, a call cost 20-25 %
# more). A strided read of VMEM takes 32-bit rows of 128 lanes, and a
# bfloat16 slot packs two neighbouring rows — two K/V heads of one key — a
# word: so the slot is VIEWED as 32-bit words, the read takes the words that
# hold the head, and the head's half of each is moved out (``_word_runs``,
# ``_head_rows``: nothing is cast, nothing copied). (Until PR 60 the group's K
# and V were cast into float32 scratch first and read out of that: on 32 K/V
# heads that cast and its traffic were half of a call — 3,062 us a 512-token
# call over 3,072 rows, 1,390 now; rounding the float32 read back to bfloat16
# for the products, the scratch kept, 2,975: PERF.md PR 60.) GQA needs no
# ``jnp.repeat`` of K/V either way.
#
# Both products take their operands in the type the queries and the pools
# hold — bfloat16 on a server, float32 in the CPU tests' plain cases — and
# sum in float32; the probabilities are rounded to that type for the value
# product, as the narrow and the latent kernel's are. Maxima, denominators,
# the scale, the mask and the sink stay float32.

#: folded query rows (positions x heads) per program: bounds VMEM whatever
#: the chunk length is ([rows, 128] f32 score tiles of 512 KiB)
_PAGED_ROWS = 1024

#: what a program's walk may hold in VMEM where the core has plenty. A v5e
#: core has 128 MiB of it and a kernel gets 16 by default, so the call asks
#: for its own limit: this budget, the query and output blocks and the
#: accumulators (up to 3 MiB at 1,024 rows) and as much again for the
#: compiler's temporaries. Measured on a v5e (PERF.md, PR 41): a 1,024-row
#: chunk tile costs 2.25 us a page at one page a step, 1.56 at two, 0.74 at
#: five, 0.62 at ten, 0.56 at sixteen (the row reductions and the
#: accumulators' read-modify-write are paid a step, not a page)
_PAGED_WALK_BYTES = 24 << 20


def _walk_budget() -> int:
    """The walk's VMEM budget on the chip this process drives: a fifth of a
    core's VMEM, at most ``_PAGED_WALK_BYTES`` (a v5e's or a v6e's 128 MiB
    give all 24 MiB, a v5p's 64 give 12.8, a v4's 16 give 3.2, so the limit
    the call asks for, twice the budget and 8 MiB, fits each of them)."""
    from jax.experimental.pallas import tpu as pltpu

    try:
        vmem = pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:  # no TPU here: interpreted, or compiled for a described v5e
        vmem = 128 << 20
    return min(_PAGED_WALK_BYTES, vmem // 5)


#: pages a group may take whatever the budget allows: each page is two
#: copies (K and V) issued one by one, and a decode step gains nothing past
#: sixteen (l6, 16 lanes: 236 us a call at 4, 178 at 8, 155 at 16, 161 at 32).
#: Measured again where a stretch of ``PAGE_RUN`` pages is one copy and a
#: program's first group is handed on (PERF.md, PR 61; us a decode call a
#: layer over a table laid in runs, at 8 / 16 / 32 / 64): mistral_l6 145.7,
#: 129.1, 122.6, 126.3; mistral_tp4_local 80.6, 60.8, 52.5, 50.8;
#: falconh1_l4 421.4, 310.2, 265.7, 282.3; kexaone_l5_full 633.8, 517.0,
#: 521.8, 542.5; mimo_l7_full (at 16 / 32 / 64) 1,635.6, 1,502.7, 1,503.8;
#: evabyte_l8 (32 K/V heads: 32 pages are all its budget fits) at 1,152 /
#: 2,048 / 2,944 rows 536.1, 542.6 / 963.0, 988.4 / 1,315.1, 1,315.6 — 32
#: would gain 5-14 % on five points (a step's fixed costs, no longer its
#: copies' issue, are what a larger group saves) and cost evabyte_l8 0-2.6 %.
#: NOT taken in PR 61: nothing end to end was measured on it (ROADMAP S11)
_PAGED_GROUP_MAX = 16

#: and where the pools hold ONE K/V head wider than a 128-lane run (a head a
#: layer of a pool: ``GqaSpec.split_heads``), so that a page adds only
#: ``page`` columns under the call's few rows — measured there and nowhere
#: else: 128 lanes at 3,072 keys of 256, 8 query heads a call, 3,609 us at
#: 16, 3,218 at 32, 2,965 at 64 (PERF.md, PR 49; 2,947 at 64 under bfloat16
#: products, the parent beside it 2,961: the copies bind it, PR 60 — and they
#: did: a run of 8 pages a copy 1,544, a program's first group handed on
#: 1,407, at 64, the parent beside it 2,908; under runs 2,237.6 at 16,
#: 1,652.2 at 32, 1,403.1 at 64: PR 61)
_PAGED_ONE_HEAD_GROUP_MAX = 64

#: and a chunk tile's, a K/V head at a time, where a page adds only ``page``
#: columns a head: a 512-token call at offset 4,096 / 11,776 on MiMo's full
#: layers 1,926 / 5,019 us at 16, 1,614 / 4,154 at 32, 1,669 / 4,022 at 64;
#: at 2,048 on K-EXAONE's 989, 961, 1,068; at 512 on l6 104, 116, 116 (the
#: walk's last group is multiplied whole, its dead pages too: PERF.md, PR 43).
#: Measured again under bfloat16 products (PERF.md, PR 60), us at 32 / 64:
#: MiMo's full layers at 1,024 / 4,096 / 11,776 565, 637 / 1,504, 1,520 /
#: 3,864, 3,636; K-EXAONE's at 512 / 2,048 / 7,680 409, 412 / 787, 851 /
#: 2,278, 2,076; one K/V head of 256 (Qwen3-Next's) at 2,048 / 7,680 295, 315
#: / 758, 725; 32 K/V heads (EvaByte's; 45 fit) over 2,176 / 3,072 / 3,968
#: rows 1,052, 1,093 / 1,395, 1,458 / 1,736, 1,813: 64 gains 4-9 % past ~8k
#: keys and loses 5-12 % under ~3k, where the cells' chunks are: it stays
_PAGED_CHUNK_GROUP_MAX = 32


def query_tile(c: int, heads: int) -> int:
    """Positions of a call's ``c`` a (row, query tile) program takes: the
    whole chunk when it is small (block == array, any C), else a multiple of
    8 positions so the block's row count tiles."""
    return c if c * heads <= _PAGED_ROWS else max(8, _PAGED_ROWS // heads // 8 * 8)


def kernel_walks(dh: int, dv: int, interpret: bool, kvh: int = 0) -> bool:
    """Whether the kernel's own copies can take the pools' pages where the
    call is compiled for a chip (interpreted, any width goes): a copy takes
    whole 128-lane rows, so a head of a multiple of 128 lanes as held, its
    pools ``[.., kv_heads, width]`` — or, ``kvh`` > 0, a narrower head whose
    pools are row-major ``[.., kv_heads * width]``: keys and values of one
    width, a token's heads whole runs (``narrow_run``)."""
    if interpret:
        return True
    if kvh:
        return dh == dv and kvh * dh % math.lcm(dh, 128) == 0
    return dh % 128 == 0 and dv % 128 == 0


def narrow_run(kvh: int, dh: int) -> int:
    """Lanes of a row-major pool's token (``kvh`` heads of ``dh`` side by
    side) the narrow-head walk multiplies at a time: the fewest whole
    128-lane rows that hold whole heads (128 for heads of 32 or 64, 384 for
    four heads of 96) where a token is whole such runs, else (interpreted
    only) the whole token."""
    run = math.lcm(dh, 128)
    return run if kvh * dh % run == 0 else kvh * dh


def per_kv_head(tile_c: int, heads: int, kvh: int) -> bool:
    """Whether a query tile's two products are made a K/V head at a time,
    over that head's own query rows (a chunk), or once for all heads with
    the other heads' columns masked (a decode step): per head where a head's
    rows of the tile, ``tile_c`` positions x its query heads, are whole
    float32 sublane tiles — and the K/V heads one or an even number: a
    bfloat16 pool packs two heads' rows a 32-bit word, and an odd number
    would pack a key's last head with the next key's first
    (``_head_rows``). The ONE predicate: the kernel's walk cuts its
    tile by it and the server counts its programs by it (``tpu/serving.py``:
    ``arkflow_gen_attn_tiles_total``). (A row-major pool's walk,
    ``_narrow_kernel``, multiplies a 128-lane run of heads at a time over
    those heads' own query rows whatever the tile: counted per head.)"""
    return (tile_c > 1 and tile_c * (heads // kvh) % 8 == 0
            and (kvh == 1 or kvh % 2 == 0))


def _page_group(rows: int, page: int, kvh: int, dh: int, itemsize: int,
                dv: int = 0, per_head: bool = False, parts: int = 1) -> int:
    """Pages a program takes per step of its walk, from the shapes it is
    called with (``dh`` the keys' width as held, in ``parts`` parts; ``dv``
    the values' where it is another). A page costs VMEM in two places: its K
    and V rows, twice (two slots) in the pools' type — the products take
    them as held, no float32 copy —, and its columns of every [rows,
    columns] float32 tile the softmax holds at once (scores, probabilities,
    the mask's bounds: four). All heads at once, a page is ``page * kvh``
    columns under all ``rows``: a decode step's 8-64 folded rows leave room
    for many pages, so its groups stop at ``_PAGED_GROUP_MAX``
    (``_PAGED_ONE_HEAD_GROUP_MAX`` over pools of one K/V head of more than
    128 lanes). A K/V head at a time (``per_head``, a chunk tile) it is
    ``page`` columns under that head's ``rows / kvh`` rows, and the head's K
    and V rows once more as read out of the slot (32-bit words, then the
    pools' type); a slot of several heads wider than one 128-lane run is
    copied once more for that read (``_word_runs``)."""
    cols = page * kvh
    kv = (dh + (dv or dh)) // 2
    if per_head:
        copied = kvh > 1 and max(dh // parts, dv or dh) > 128
        a_page = (cols * kv * (4 + 2 * copied) * itemsize
                  + page * kv * (8 + 2 * itemsize) + page * (rows // kvh) * 16)
        return _whole_runs(min(_PAGED_CHUNK_GROUP_MAX, _walk_budget() // a_page))
    a_page = cols * (kv * 4 * itemsize + rows * 16)
    most = (_PAGED_ONE_HEAD_GROUP_MAX if kvh == 1 and dh > 128
            else _PAGED_GROUP_MAX)
    return _whole_runs(min(most, _walk_budget() // a_page))


def _whole_runs(fit: int) -> int:
    """A group of at most ``fit`` pages that ``PAGE_RUN`` divides wherever it
    holds a stretch (the ceilings do; one the budget bounds is rounded
    down, as ``_latent_group`` rounds to whole lane tiles), so that its walk
    takes runs; at least a page."""
    run = PAGE_RUN or 1
    return max(1, fit if fit < run else fit // run * run)


def _lane_runs(width: int) -> list[tuple[int, int]]:
    """``width`` lanes as the runs a strided read of VMEM takes: of 128
    (the chip's; a narrower or ragged width, interpreted only, whole)."""
    run = 128 if width % 128 == 0 else width
    return [(a, a + run) for a in range(0, width, run)]


def _word_runs(buf, slot, spare):
    """``buf[slot]``, a group's rows ordered (key, K/V head), as refs of
    32-bit WORDS a 128-lane run wide, which is what a strided read of VMEM
    takes: a bfloat16 pool packs two neighbouring rows a word — K/V heads
    2i and 2i + 1 of one key —, so no read picks ONE head's 16-bit rows, but
    one picks the words that hold it (``_head_rows``). A slot of one run is
    viewed in place, nothing copied; a wider one ("the last dim size is not
    128 in original base memref") is copied run by run into the next of the
    ``spare`` scratch refs (``_walk_call``'s ``word_scratch``)."""
    view = buf.bitcast(jnp.uint32)
    runs = _lane_runs(buf.shape[-1])
    if len(runs) == 1:
        return [view.at[slot]]
    out = [next(spare) for _ in runs]
    for ref, (a, z) in zip(out, runs):
        ref[...] = view[slot, :, a:z]
    return out


def _head_rows(words, j: int, keys: int, kvh: int, dtype):
    """K/V head ``j``'s row of each of ``keys`` keys out of ``_word_runs``'s
    words, in the pools' ``dtype``: the words at a stride of the heads'
    words a key, and of each word the head's bits (exact: a move)."""
    pack = 4 // jnp.dtype(dtype).itemsize               # rows a word
    assert kvh % pack == 0, (kvh, dtype)                # ``per_kv_head``
    got = words[pl.ds(j // pack, keys, stride=kvh // pack), :]
    if pack == 1:
        return jax.lax.bitcast_convert_type(got, dtype)
    bits = 32 // pack
    return jax.lax.bitcast_convert_type(
        (got >> (bits * (j % pack))).astype(jnp.dtype(f"uint{bits}")), dtype)


def _window_start(first, window: int, cols: int):
    """The first page GROUP a tile whose first query sits at ``first`` walks
    under a lower bound: the one that holds ``first - (window - 1)``."""
    return jnp.maximum(first - (window - 1), 0) // cols


def _paged_kernel(off_ref, table_ref, q_ref, *rest, page: int,
                  kvh: int, heads: int, tile_c: int, group: int, ring: int,
                  window: int = 0, scale: float = 0.0, sink: bool = False,
                  parts: int = 1, part_stride: int = 0, per_head: bool = False):
    from jax.experimental.pallas import tpu as pltpu

    if sink:  # [rows, 1] float32: each folded row's head's sink logit
        sink_ref, *rest = rest
    if per_head:
        # the accumulators ride the walk's loop, not VMEM scratch; ``words``:
        # where a slot is wider than one 128-lane run, its rows once more in
        # runs a strided read takes (``_word_runs``)
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, slot_ref, *words = rest
    else:
        (k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, slot_ref,
         o_acc, m_acc, l_acc) = rest
    bi = pl.program_id(0)
    ci = pl.program_id(1)
    rows, d = q_ref.shape[1], q_ref.shape[2]
    cols = page * kvh
    width = group * cols

    def walked(bi, ci):
        """Program (``bi``, ``ci``)'s first query and the logical pages lo
        .. hi - 1 it walks. Folded row r is (chunk position ci*tile_c +
        r // heads, q head r % heads) at absolute position off + that — per
        head: (kv head r // (rows / kvh), position, q head of its group) —;
        the tile's last attendable key is its last query's position, so the
        walk ends at that page. The table's width bounds it too: queries
        padded past a chunk may sit past the last column (a ring has no last
        column: it wraps)."""
        first = off_ref[bi] + ci * tile_c
        hi = (first + (tile_c - 1)) // page + 1
        if window:  # the walk starts at the window's first page, not at 0
            return first, _window_start(first, window, page), hi
        return first, 0, jnp.minimum(hi, ring)

    first, lo, hi = walked(bi, ci)

    def copies(slot, j, src, n=1):
        """The K and the V copy of ``n`` pages from ``src`` on — side by
        side in the pools, which ride as flat rows, ``cols`` a page — into
        ``slot``, the ``j``-th page of a group on: ONE copy a pool however
        many pages (a key held in parts: a copy a part, the parts
        ``part_stride`` pages apart, into slots 2p + slot)."""
        def at(page0):  # the rows of ``n`` pages from ``page0`` on
            return pl.ds(pl.multiple_of(page0 * cols, cols), n * cols)

        dst = at(j)
        return (*(pltpu.make_async_copy(k_hbm.at[at(src + p * part_stride)],
                                        k_buf.at[2 * p + slot, dst],
                                        sems.at[0, slot])
                  for p in range(1, parts)),
                pltpu.make_async_copy(k_hbm.at[at(src)], k_buf.at[slot, dst],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[at(src)], v_buf.at[slot, dst],
                                      sems.at[1, slot]))

    # a ring's tile starts at an unaligned page: no runs there (nor in a
    # pool that holds less than one)
    run = PAGE_RUN if _takes_runs(
        window, group, min(k_hbm.shape[0], v_hbm.shape[0]) // cols) else 0

    def walk(bi, lo, hi, **kw):
        return _page_walk(
            copies, lambda i: table_ref[bi, i % ring if window else i], lo, hi,
            group, run, **kw)

    opens = jnp.logical_and(bi == 0, ci == 0)

    @pl.when(opens)
    def _finite():
        # a group's dead pages keep what the slot held: rows of the pool or,
        # before the call's first copy, whatever VMEM held. Their columns
        # are masked, but a probability of 0 times a NaN is a NaN
        v_buf[...] = jnp.zeros_like(v_buf)
        slot_ref[0] = 0

    # the programs run one after another on one core, so a program's LAST
    # step starts the first group of the program after it, into the slot it
    # is not reading (whose number rides in SMEM): a walk's first group is
    # the one that nothing of its own program hides (PERF.md, PR 61: a
    # decode call of 16 lanes at 13 / 29 / 61 / 125 pages a lane 62.5 / 84.8
    # / 130.7 / 218.2 us each program starting its own, 47.9 / 69.5 / 118.2 /
    # 204.0 handed on). The call's first program starts its own
    ci_next = jnp.where(ci + 1 < pl.num_programs(1), ci + 1, 0)
    bi_next = bi + (ci_next == 0)
    more = bi_next < pl.num_programs(0)
    bi_next = jnp.where(more, bi_next, bi)
    _, start_next, _ = walk(bi_next, *walked(bi_next, ci_next)[1:])

    def hand_on(slot):
        @pl.when(more)
        def _next():
            slot_ref[0] = slot
            start_next(0, slot)

    steps, start, arrive = walk(bi, lo, hi, first_slot=slot_ref[0], hand_on=hand_on)
    pl.when(opens)(lambda: start(0, 0))

    scale = scale or 1.0 / math.sqrt(d)
    dims = (((1,), (1,)), ((), ()))
    # both products take their operands in the type the queries and the
    # pools agree on — a server's are both the pools', bfloat16, and nothing
    # is cast —, the sums float32; the probabilities are rounded to it for
    # the value product, as the narrow and the latent kernel's
    op = jnp.promote_types(q_ref.dtype, k_buf.dtype)

    def kept(g, bound, ahead):
        """The g-th group's mask from the bounds worked out once: ``base +
        key <= q_pos`` is ``base <= bound``, the window's lower bound
        likewise."""
        base = (lo + g * group) * page
        keep = base <= bound
        if window:
            keep = jnp.logical_and(keep, base > ahead - window)
        return keep

    if per_head:
        # a head's [rows / kvh, group * page] tile: row r is position
        # r // (heads / kvh) of the tile, column c key c of the group;
        # nothing to mask across heads
        mine, keys = rows // kvh, group * page
        r = jax.lax.broadcasted_iota(jnp.int32, (mine, keys), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (mine, keys), 1)
        ahead = first + r // (heads // kvh) - c

        def head_by_head(g, acc):
            slot = arrive(g)
            if kvh > 1:  # the slots as 32-bit words, a ref a 128-lane run
                spare = iter(words)
                k_words = [ref for p in range(parts)
                           for ref in _word_runs(k_buf, 2 * p + slot, spare)]
                v_words = _word_runs(v_buf, slot, spare)
            keep = kept(g, ahead, ahead)
            out = []
            for j, (o, m, l) in enumerate(acc):
                at = slice(j * mine, (j + 1) * mine)
                if kvh == 1:  # the group's rows ARE the head's
                    k = [k_buf[2 * p + slot] for p in range(parts)]
                    v = v_buf[slot]
                else:
                    k = [_head_rows(ref, j, keys, kvh, k_buf.dtype) for ref in k_words]
                    v = jnp.concatenate([_head_rows(ref, j, keys, kvh, v_buf.dtype)
                                         for ref in v_words], axis=1)
                w = d // len(k)
                scores = functools.reduce(operator.add, (jax.lax.dot_general(
                    q_ref[0, at, i * w:(i + 1) * w].astype(op), k_i.astype(op),
                    dims, preferred_element_type=jnp.float32)
                    for i, k_i in enumerate(k))) * scale          # [mine, keys]
                scores = jnp.where(keep, scores, _NEG)
                m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
                p = jnp.exp(scores - m_new)
                corr = jnp.exp(m - m_new)
                out.append((o * corr + jax.lax.dot_general(
                    p.astype(op), v.astype(op), (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32),
                    m_new, l * corr + p.sum(axis=-1, keepdims=True)))
            return tuple(out)

        # (sum, maximum, denominator) a head; a sink is one more key with
        # score b_h and no value: maximum b_h, denominator exp(b_h - b_h)
        acc = jax.lax.fori_loop(0, steps, head_by_head, tuple(
            (jnp.zeros((mine, o_ref.shape[2]), jnp.float32),
             sink_ref[j * mine:(j + 1) * mine, :] if sink
             else jnp.full((mine, 1), _NEG, jnp.float32),
             jnp.full((mine, 1), 1.0 if sink else 0.0, jnp.float32))
            for j in range(kvh)))
        for j, (o, _, l) in enumerate(acc):  # l is never truly zero: below
            o_ref[0, j * mine:(j + 1) * mine, :] = (
                o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        return

    o_acc[:] = jnp.zeros_like(o_acc)
    if sink:
        # the sink is one more key with score b_h and no value: the running
        # maximum starts at it, the denominator at exp(b_h - b_h) = 1
        m_acc[:] = jnp.broadcast_to(sink_ref[...], m_acc.shape)
        l_acc[:] = jnp.ones_like(l_acc)
    else:
        m_acc[:] = jnp.full_like(m_acc, _NEG)
        l_acc[:] = jnp.zeros_like(l_acc)

    # the mask, but for the group's first position: column c of a group is
    # key (c // cols) * page + (c % cols) // kvh of kv head c % kvh, and the
    # bound is -1, below every base, on the other kv heads than the row's own
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    ahead = first + r // heads - ((c // cols) * page + (c % cols) // kvh)
    own_head = (r % heads) // (heads // kvh) == c % kvh
    bound = jnp.where(own_head, ahead, -1)
    q = q_ref[0].astype(op)                                       # [rows, D]
    w = d // parts

    def body(g, _):
        slot = arrive(g)
        # a key in parts: the products of each part's lanes, summed
        scores = functools.reduce(operator.add, (jax.lax.dot_general(
            q[:, p * w:(p + 1) * w] if parts > 1 else q,
            k_buf[2 * p + slot].astype(op),                       # [width, D]
            dims, preferred_element_type=jnp.float32)
            for p in range(parts))) * scale
        scores = jnp.where(kept(g, bound, ahead), scores, _NEG)   # [rows, width]
        m = m_acc[:, :1]                                          # [rows, 1]
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m - m_new)
        l_acc[:] = jnp.broadcast_to(
            l_acc[:, :1] * corr + p.sum(axis=-1, keepdims=True), l_acc.shape)
        m_acc[:] = jnp.broadcast_to(m_new, m_acc.shape)
        o_acc[:] = o_acc[:] * corr + jax.lax.dot_general(
            p.astype(op), v_buf[slot].astype(op), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    jax.lax.fori_loop(0, steps, body, None)
    # every query admits at least key 0 of its own kv head (k_pos=0 <= q_pos
    # always) and the walk starts at page 0, so m is real before any
    # fully-masked group arrives and l is never truly zero; the floor only
    # guards numerical underflow. Under a lower bound a group may hold no
    # key of a query whose own key comes later in the walk: what it summed
    # meanwhile is scaled to 0 there
    o_ref[0] = (o_acc[:] / jnp.maximum(l_acc[:, :1], 1e-30)).astype(o_ref.dtype)


def _walk_call(b, tiles, rows, dh, dtype, *, page, kvh, heads, tile_c, ring,
               window, dv=0, scale=0.0, sink=False, parts=1, part_stride=0):
    """``_paged_kernel``'s kernel, grid and compiler parameters (``dh`` the
    keys' width as held, in ``parts`` parts ``part_stride`` pages apart;
    ``dv`` the values' where it is another; ``scale`` the scores' where the
    keys are held wider than they are; ``sink``: one more operand, each
    folded row's sink logit)."""
    from jax.experimental.pallas import tpu as pltpu

    per_head = per_kv_head(tile_c, heads, kvh)
    group = _page_group(rows, page, kvh, dh, dtype.itemsize, dv, per_head, parts)
    if per_head and window:
        # a tile's whole walk under a window: pages past it would be dead
        # columns of every step's products
        group = min(group, (window + tile_c - 2) // page + 2)
    dv = dv or dh

    def _q_index(bi, ci, *_):
        return (bi, ci, 0)

    def word_scratch(width):
        """``_word_runs``'s scratch for a slot ``width`` lanes wide: none
        where the per-head cut reads the slot in place."""
        runs = _lane_runs(width)
        return [] if kvh == 1 or len(runs) == 1 else [
            pltpu.VMEM((group * page * kvh * dtype.itemsize // 4, z - a),
                       jnp.uint32) for a, z in runs]

    kernel = functools.partial(
        _paged_kernel, page=page, kvh=kvh, heads=heads, tile_c=tile_c,
        group=group, ring=ring, window=window, scale=scale, sink=sink,
        parts=parts, part_stride=part_stride, per_head=per_head)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, tiles),
        in_specs=[
            pl.BlockSpec((1, rows, dh), _q_index),
            *([pl.BlockSpec((rows, 1), lambda *_: (0, 0))] if sink else []),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, rows, dv), _q_index),
        scratch_shapes=[
            pltpu.VMEM((2 * parts, group * page * kvh, dh // parts), dtype),
            pltpu.VMEM((2, group * page * kvh, dv), dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),
            *(word_scratch(dh // parts) * parts + word_scratch(dv)
              if per_head else [pltpu.VMEM((rows, dv), jnp.float32),
                                pltpu.VMEM((rows, 128), jnp.float32),
                                pltpu.VMEM((rows, 128), jnp.float32)]),
        ],
    )
    # programs run one after another on one core: the slots are zeroed by
    # the first and carry pool rows from then on (``_finite``)
    return kernel, grid_spec, {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=2 * _walk_budget() + (8 << 20))}


#: pages a step of the narrow-head walk takes where the budget allows more:
#: a page is ``page`` columns of its tiles, as the latent kernel's. On a v5e,
#: us a call a layer at 16 / 32 / 64 pages, 32 / 8 heads of 64 (PERF.md, PR
#: 46): a decode step of 128 lanes at contexts 512 / 2,048 / 4,863 635, 582,
#: 507 / 1,904, 1,513, 1,368 / 4,116, 3,254, 2,832; a 256-token chunk at
#: offsets 512 / 2,048 / 4,608 194, 185, 179 / 308, 275, 287 / 506, 429, 411
_NARROW_GROUP_MAX = 64


def _narrow_group(mine: int, runs: int, page: int, lanes: int, itemsize: int,
                  window: int, tile_c: int) -> int:
    """Pages a program of the narrow-head walk takes a step: a page costs
    VMEM as its K and V rows (``lanes`` wide, two slots each) and as
    ``page`` columns of every [rows, columns] float32 tile the softmax holds
    (five, a run's rows at a time but all runs' sums alive). Whole 128-lane
    score tiles where a group has that many keys; a tile's whole walk under
    a window."""
    fit = max(1, _walk_budget() // (
        page * (lanes * 4 * itemsize + mine * runs * 20)))
    group = min(fit, _NARROW_GROUP_MAX)
    if window:
        group = min(group, (window + tile_c - 2) // page + 2)
    per_tile = max(1, 128 // page)
    return group if group < per_tile else group // per_tile * per_tile


def _narrow_kernel(layer_ref, off_ref, table_ref, q_ref, k_hbm, v_hbm, o_ref,
                   k_buf, v_buf, sems, *, page: int, tile_c: int, hpr: int,
                   mine: int, group: int, ring: int, window: int, scale: float):
    """The walk over ROW-MAJOR pools [layers, pages, page, kv_heads * dh]
    (a head narrower than 128 lanes): a page is copied as it is held, every
    head of its tokens side by side, and the products are made a 128-lane
    RUN of heads at a time. Folded row r of a program is (run r // mine,
    position (r % mine) // hpr of the tile, one of the run's ``hpr`` query
    heads), its query ZERO-EXTENDED over the run — the head's ``dh`` values
    at its K/V head's lanes, zeros at the run's other heads' — so
    ``q . k_run`` over all 128 lanes is the score against its own head's
    key and a column is a KEY, nothing to mask across heads; ``p . v_run``
    gives every head's values under the row's probabilities, and the
    wrapper keeps its own head's lanes. (Chosen over lane slices of a head:
    a 64-lane slice of a VMEM row is a relayout a step, and the product is
    bound by loading the keys into the MXU either way: its depth, 128 for
    64, is not what it waits for.) Operands go to the MXU in the pools'
    type, sums in float32; the probabilities are rounded to the values'
    type, as the latent kernel's."""
    from jax.experimental.pallas import tpu as pltpu

    bi = pl.program_id(0)
    ci = pl.program_id(1)
    run = q_ref.shape[-1]
    runs = q_ref.shape[0] // mine
    width = group * page
    first = off_ref[bi] + ci * tile_c
    hi = (first + (tile_c - 1)) // page + 1
    if window:
        lo = _window_start(first, window, page)
    else:
        lo, hi = 0, jnp.minimum(hi, ring)
    layer = layer_ref[0]

    def copies(slot, j, src):
        dst = pl.ds(pl.multiple_of(j * page, page), page)
        return (pltpu.make_async_copy(k_hbm.at[layer, src], k_buf.at[slot, dst],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, src], v_buf.at[slot, dst],
                                      sems.at[1, slot]))

    steps, start, arrive = _page_walk(
        copies, lambda i: table_ref[bi, i % ring if window else i], lo, hi, group)

    @pl.when(jnp.logical_and(bi == 0, ci == 0))
    def _finite():
        # a group's dead pages keep what the slot held (``_paged_kernel``):
        # masked, but a probability of 0 times a NaN is a NaN
        v_buf[...] = jnp.zeros_like(v_buf)

    start(0, 0)
    # rows past the tile's (``mine`` is rounded up to whole sublane tiles)
    # take its last position: finite, dropped by the wrapper
    r = jax.lax.broadcasted_iota(jnp.int32, (mine, width), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (mine, width), 1)
    ahead = first + jnp.minimum(r // hpr, tile_c - 1) - c
    dims = (((1,), (1,)), ((), ()))

    def body(g, acc):
        slot = arrive(g)
        base = (lo + g * group) * page
        keep = base <= ahead
        if window:
            keep = jnp.logical_and(keep, base > ahead - window)
        out = []
        for j, (o, m, l) in enumerate(acc):
            lanes = slice(j * run, (j + 1) * run)
            k, v = k_buf[slot, :, lanes], v_buf[slot, :, lanes]   # [width, run]
            scores = jax.lax.dot_general(
                q_ref[j * mine:(j + 1) * mine, :], k, dims,
                preferred_element_type=jnp.float32) * scale       # [mine, width]
            scores = jnp.where(keep, scores, _NEG)
            m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
            p = jnp.exp(scores - m_new)
            corr = jnp.exp(m - m_new)
            out.append((o * corr + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32),
                m_new, l * corr + p.sum(axis=-1, keepdims=True)))
        return tuple(out)

    acc = jax.lax.fori_loop(0, steps, body, tuple(
        (jnp.zeros((mine, run), jnp.float32),
         jnp.full((mine, 1), _NEG, jnp.float32),
         jnp.zeros((mine, 1), jnp.float32)) for _ in range(runs)))
    for j, (o, _, l) in enumerate(acc):  # l is never truly zero: ``_paged_kernel``
        o_ref[j * mine:(j + 1) * mine, :] = (
            o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _narrow_attention(q, k_pages, v_pages, layer, page_table, off, *,
                      interpret: bool, window: int):
    """``paged_flash_attention`` over row-major pools [layers, pages, page,
    kv_heads * dh] (``_narrow_kernel``): folds the queries (run of heads,
    position, query head of the run), zero-extends each over its run, and
    keeps each row's own head's lanes of what comes back."""
    from jax.experimental.pallas import tpu as pltpu

    b, c, h, dh = q.shape
    page, lanes = k_pages.shape[2:]
    kvh = lanes // dh
    if lanes % dh or h % kvh or v_pages.shape != k_pages.shape:
        raise ValueError(
            f"row-major pools hold kv heads x {dh} lanes a token, keys and "
            f"values alike, the {h} query heads a multiple of the kv heads; "
            f"got {k_pages.shape} and {v_pages.shape}")
    if not kernel_walks(dh, dh, interpret, kvh):
        raise ValueError(
            "a head narrower than 128 lanes is walked on a chip where a "
            "token's heads are whole runs of lcm(width, 128) lanes; "
            f"got {kvh} kv heads of {dh}")
    run = narrow_run(kvh, dh)
    per_run, runs, gq = run // dh, lanes // run, h // kvh
    hpr = per_run * gq
    tile_c = query_tile(c, h)
    tiles = -(-c // tile_c)
    sub = 32 // q.dtype.itemsize          # rows of a packed sublane tile
    mine = -(-tile_c * hpr // sub) * sub
    group = _narrow_group(mine, runs, page, lanes, k_pages.dtype.itemsize,
                          window, tile_c)
    eye = jnp.eye(per_run, dtype=q.dtype)
    q = jnp.pad(q, ((0, 0), (0, tiles * tile_c - c), (0, 0), (0, 0)))
    q = jnp.einsum("btcrjgd,jk->btrcjgkd",
                   q.reshape(b, tiles, tile_c, runs, per_run, gq, dh), eye)
    q = jnp.pad(q.reshape(b, tiles, runs, tile_c * hpr, run),
                ((0, 0),) * 3 + ((0, mine - tile_c * hpr), (0, 0)))
    rows = runs * mine

    def _q_index(bi, ci, *_):
        return (bi, ci, 0)

    kernel = functools.partial(
        _narrow_kernel, page=page, tile_c=tile_c, hpr=hpr, mine=mine,
        group=group, ring=page_table.shape[1], window=window,
        scale=1.0 / math.sqrt(dh))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, tiles),
        in_specs=[pl.BlockSpec((None, rows, run), _q_index),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, rows, run), _q_index),
        scratch_shapes=[pltpu.VMEM((2, group * page, lanes), k_pages.dtype),
                        pltpu.VMEM((2, group * page, lanes), v_pages.dtype),
                        pltpu.SemaphoreType.DMA((2, 2))])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, tiles * rows, run), q.dtype),
        interpret=interpret,
        name=PAGED_WINDOW_NAME if window else "paged_flash_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * _walk_budget() + (8 << 20)),
    )(jnp.asarray(layer, jnp.int32).reshape(1), jnp.asarray(off, jnp.int32),
      jnp.asarray(page_table, jnp.int32), q.reshape(b, tiles * rows, run),
      k_pages, v_pages)
    out = out.reshape(b, tiles, runs, mine, run)[:, :, :, :tile_c * hpr]
    out = jnp.einsum("btrcjgkd,jk->btcrjgd", out.reshape(
        b, tiles, runs, tile_c, per_run, gq, per_run, dh), eye)
    return out.reshape(b, tiles * tile_c, h, dh)[:, :c]


#: the windowed call's name in a device trace (the full call keeps the
#: jitted function's own, ``paged_flash_attention``)
PAGED_WINDOW_NAME = "paged_window_attention"


@functools.partial(jax.jit, static_argnames=("interpret", "window"))
def paged_flash_attention(q, k_pages, v_pages, layer, page_table, off, *,
                          interpret: bool = False, window: int = 0, sink=None):
    """Flash attention that reads K/V straight from the serving page pools.

    q: [B, C, H, dh] — C queries per row at absolute positions
    ``off[b] + i`` (decode: C=1, off=lengths; chunked prefill: off=chunk
    offset). k_pages/v_pages: [layers, num_pages, page, kv_heads, dh] (the
    WHOLE pools, left in HBM; ``layer`` picks the pages the kernel copies,
    so the layer loop never slices a pool). page_table: [B, P] int32 —
    entries past a row's context are never looked at. off: [B] int32.

    Query i attends keys 0..off+i — exactly the dense-gather reference's
    ``key_pos <= positions`` mask — with GQA resolved inside the kernel
    (no ``jnp.repeat`` of K/V). Returns [B, C, H, dh] in q's dtype.

    ``window`` > 0 (a sliding layer) bounds the keys below too — the query
    at t attends ``t - window < s <= t`` — and ``page_table`` is then a
    RING over the layer's window pool: logical page i sits in column
    ``i % columns``. A (row, query tile) walks only the pages its window
    touches, from the one that holds its first query's oldest key, and the
    call is named ``paged_window_attention`` in a trace.

    The values may be of another width than the keys (the output is the
    values' wide), and a key may be HELD in parts (``GqaSpec.key_parts``):
    ``k_pages`` is then [parts * layers, ..., 128], layer ``l``'s part ``p``
    at ``p * layers + l``, the last part padded with zeros, so that the walk
    below can copy its pages in whole lanes. The queries are padded
    likewise here and the scores scaled by the queries' own width. ``sink``
    [H] float32: one logit a query head
    that joins every softmax and adds no value (the online softmax starts
    at it: maximum ``sink``, denominator 1, sum 0).

    A head narrower than 128 lanes has ROW-MAJOR pools, [layers, num_pages,
    page, kv_heads * dh] (keys and values of one width, no sink), and the
    walk of ``_narrow_kernel``.
    """
    if k_pages.ndim == 4:
        if sink is not None:
            raise ValueError("a sink is served at heads of 128 lanes' "
                             "multiples: row-major pools have none")
        return _narrow_attention(q, k_pages, v_pages, layer, page_table, off,
                                 interpret=interpret, window=window)
    b, c, h, dq = q.shape
    layers, n_pages, page, kvh, dh = k_pages.shape
    dv = v_pages.shape[-1]
    parts = -(-dq // dh)
    dh = parts * dh  # a key as held: its parts side by side
    if h % kvh:
        raise ValueError(f"q heads {h} must be a multiple of kv heads {kvh}")
    # the layer rides in the page index: the pools are viewed as one run of
    # layers * num_pages pages (flat rows, page * kv_heads a page) and the
    # table names pages of that run
    table = (jnp.asarray(page_table, jnp.int32)
             + jnp.asarray(layer, jnp.int32) * n_pages)
    tile_c = query_tile(c, h)
    c_pad = -(-c // tile_c) * tile_c
    if c_pad != c or dq != dh:
        # padded queries sit past the chunk: finite garbage, sliced off
        # below; padded lanes meet a held key's zeros
        q = jnp.pad(q, ((0, 0), (0, c_pad - c), (0, 0), (0, dh - dq)))
    rows = tile_c * h
    plain = dv == dh == dq and sink is None
    if not kernel_walks(dh, dv, interpret):
        raise ValueError(
            "pools [.., kv heads, width] are walked on a chip at keys (as "
            "held) and values of multiples of 128 lanes (a narrower head's "
            f"are row-major: cache_spec); got keys {dh}, values {dv}")
    kernel, grid_spec, params = _walk_call(
        b, c_pad // tile_c, rows, dh, k_pages.dtype, page=page, kvh=kvh,
        heads=h, tile_c=tile_c, ring=table.shape[1], window=window,
        **({} if plain else dict(dv=dv, scale=dq ** -0.5, sink=sink is not None,
                                 parts=parts,
                                 part_stride=layers // parts * n_pages)))
    # a tile's rows: (position, query head) or, where the kernel's walk
    # multiplies a K/V head at a time, (kv head, position, its query heads)
    tiles, group = c_pad // tile_c, h // kvh
    per_head = kernel.keywords.get("per_head", False)
    if per_head:
        q = q.reshape(b, tiles, tile_c, kvh, group, dh).transpose(0, 1, 3, 2, 4, 5)
    sinks = [] if sink is None else [
        jnp.broadcast_to(sink.astype(jnp.float32).reshape(kvh, 1, group),
                         (kvh, tile_c, group)).reshape(rows, 1)
        if per_head else jnp.tile(sink.astype(jnp.float32), tile_c)[:, None]]
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c_pad * h, dv), q.dtype),
        interpret=interpret,
        **params,
        **({"name": PAGED_WINDOW_NAME} if window else {}),
    )(jnp.asarray(off, jnp.int32), table, q.reshape(b, c_pad * h, dh), *sinks,
      k_pages.reshape(layers * n_pages * page * kvh, dh // parts),
      v_pages.reshape(v_pages.shape[0] * n_pages * page * kvh, dv))
    if per_head:
        out = out.reshape(b, tiles, kvh, tile_c, group, dv).transpose(
            0, 1, 3, 2, 4, 5)
    return out.reshape(b, c_pad, h, dv)[:, :c]


# -- latent (MLA) paged attention ---------------------------------------------
#
# Multi-head latent attention (DeepSeek-V2/V3) caches, per token and layer,
# one normed latent row ``c`` (``kv_lora_rank`` wide) and one rotated rope
# key ``k_r`` shared by every head — not per-head K and V. Decode absorbs the
# up-projection: each head's query is carried into the latent space
# (``q_lat = q_nope W_uk^T``), scored against ``c`` and ``k_r`` directly,
# and the value sum is taken over ``c`` itself (``o_lat = sum p c``; the
# caller applies ``W_uv``). So the pools hold one "KV head" for all query
# heads: a page is read ONCE and every head scores against it.
#
# The KERNEL walks the table here too, as ``_paged_kernel`` does above: the
# grid is (row, query tile), the pools stay in HBM, ``layer``, the offsets and
# the table are scalar-prefetched, and a program loops over its row's live
# pages in groups, copying each group's latent and rope-key pages itself into
# one of two VMEM slots, a group ahead (``_page_walk``). (A grid over every
# column of the table, 8 pages a step, cost ~1 us a dead step and 7-8 us a
# live one under 1,024 rows on a v5e: 2.1-2.9 x this walk's time a call,
# PERF.md PR 44.) Beside the per-head kernel:
#
# * the layer is an axis of the pools (``[layers, pages, page, width]``) and
#   a copy takes ``pool[layer, page]``, a latent page and a rope-key page;
# * the rope keys are HELD in whole 128-lane rows, zeros behind the key
#   (``models/paged_decode.cache_spec``): Mosaic sees a narrower pool padded
#   to 128 lanes and takes a copy out of it only in whole rows, so a 64-lane
#   pool could be read by a grid's BlockSpec alone. The queries' rope part is
#   padded to the held width here and meets the zeros;
# * with one shared KV head a page is ``page`` score lanes under every row,
#   so a group is many pages (``_latent_group``: 32, 512 keys a step, where a
#   walk is unbounded; a tile's whole walk under a window);
# * operands stay bfloat16 into the MXU (float32 accumulation); the
#   probabilities are rounded to bfloat16 for the value product;
# * an indexer's choice rides as one float32 block a program, [tile_c,
#   context], and a step reads its group's lanes of it.

#: pages a step of ``dsa_index_scores``'s grid attends over (8 x 16-token
#: pages = one 128-lane tile)
_LATENT_GROUP = 8

#: pages a step of the latent walk takes where the budget allows more (the
#: sums' rescaling and the row reductions are paid a step, the copies a
#: page; the walk's last group is multiplied whole, dead pages too). On a
#: v5e, us a call a layer at 8 / 16 / 32 / 64 pages (PERF.md, PR 44):
#: dots3_l5's indexed layer, a 512-token chunk at offset 4,096 8,069 /
#: 6,696 / 6,413 / 6,705 and at 11,776 20,113 / 16,333 / 15,243 / 14,696, a
#: decode step of 32 lanes 1,814 / 1,385 / 1,261 / 1,200; kanana2_l6's
#: 128-token chunk at 512 142 / 146 / 158 / 157, its 16 lanes 188 / 167 /
#: 151 / 147
_LATENT_WALK_MAX = 32


def latent_query_tile(c: int, heads: int, lat: int) -> int:
    """Positions of a call's ``c`` a (row, query tile) program of the latent
    kernel takes: the [rows, L] float32 sums and the query and output blocks
    grow with the latent width, so past 512 the rows shrink."""
    cap = _PAGED_ROWS * 512 // max(lat, 512)
    return c if c * heads <= cap else max(8, cap // heads // 8 * 8)


def _latent_group(tile_c: int, heads: int, page: int, lat: int, held: int,
                  itemsize: int, window: int = 0) -> int:
    """Pages a (row, query tile) program of the latent kernel takes a step
    of its walk, from the shapes it is called with. A page costs VMEM as its
    latent and rope rows (``held`` lanes) in two slots and as ``page``
    columns of every [rows, columns] float32 tile a step holds at once
    (scores, probabilities, the mask's bounds, the choice: five). Under a
    ``window`` a tile's whole walk — the pages from its first query's
    oldest key to its last query — is one group where it fits: one step a
    program. Whole 128-lane score tiles where a group has that many keys."""
    fit = max(1, _walk_budget() // (
        page * ((lat + held) * 2 * itemsize + tile_c * heads * 20)))
    lanes = max(1, 128 // page)
    if window:
        span = (window + tile_c - 2) // page + 2
        return min(fit, -(-span // lanes) * lanes)
    group = min(fit, _LATENT_WALK_MAX)
    return group if group < lanes else group // lanes * lanes


def _page_walk(copies, page_of, lo, hi, group: int, run: int = 0,
               first_slot=None, hand_on=None):
    """The double-buffered walk of the logical pages ``lo`` .. ``hi`` - 1 in
    groups of ``group``, ``_paged_kernel``'s shape: ``copies(slot, j, src)``
    are the copies of physical page ``src`` into ``slot`` as a group's
    ``j``-th page (of ``run`` pages from ``src`` on: ``_by_runs``),
    ``page_of(i)`` logical page ``i``'s. Returns (steps, ``start``,
    ``arrive``): ``start(0, 0)`` once, then ``arrive(g)`` a step — starts the
    group after the g-th, waits for the g-th, returns its slot. Nothing past
    ``hi``. ``first_slot``: the slot group 0 was started into, where that is
    not 0, and ``hand_on(slot)``: called in the walk's last step, before its
    wait, with the slot that step leaves free (``_paged_kernel``: a program's
    first group is started by the program before it)."""
    steps = (hi - lo + (group - 1)) // group

    def live(g):
        return jnp.minimum(hi - (lo + g * group), group)

    def start(g, slot):
        def page_at(j, _):
            for dma in copies(slot, j, page_of(lo + g * group + j)):
                dma.start()

        jax.lax.fori_loop(0, live(g), page_at, None)

    def wait(g, slot):
        def page_at(j, _):  # a wait takes its size from the copy, not its source
            for dma in copies(slot, j, 0):
                dma.wait()

        jax.lax.fori_loop(0, live(g), page_at, None)

    def arrive(g):
        slot = g % 2 if first_slot is None else (g + first_slot) % 2

        @pl.when(g + 1 < steps)
        def _ahead():
            start(g + 1, 1 - slot)

        if hand_on is not None:
            pl.when(g + 1 == steps)(lambda: hand_on(1 - slot))
        wait(g, slot)
        return slot

    if run:  # ``arrive`` finds the names at its call: the lines above stand
        start, wait = _by_runs(copies, page_of, lambda g: lo + g * group, live, run)
    return steps, start, arrive


#: pages ONE copy of the latent walk moves where a slot's table holds them
#: side by side (``_by_runs``; ``tpu/serving.py`` hands a slot its pages in
#: such blocks, ``_FreePages``): a copy's issue costs the same whatever its
#: size, and the scalar core that issues it is what a walk waits for. It
#: divides the group, or the walk takes no runs. On a v5e, us a call a layer
#: at 2 / 4 / 8 pages a copy (PERF.md, PR 54), xing4_l10's 32 decode lanes at
#: ~8.8k keys, 1,346 without runs: over a table laid in runs 1,513 / 1,288 /
#: 693, over a permuted one 1,521 / 1,294 / 1,146; its 512-token chunk at
#: offset 6,144, 2,040 without: 2,087 / 2,011 / 1,795 and 2,099 / 2,013 /
#: 1,973. NO gain under 8: where the page-at-a-time branch is short (8
#: copies at 4 pages) the test of a stretch costs what both branches cost
#: (read: compiled to predicated straight-line code; with that branch a
#: LOOP 4 pages gave 784, 8 gave 691 and a permuted table 1,464 / 1,336).
#: 16 gave 673 where 8 gave 676: the bytes lead from there on.
#: The per-head walk takes the same 8 (PERF.md, PR 61; us a DECODE call a
#: layer, the parent's page-a-copy walk / this walk without runs / with,
#: over a table laid in runs, then with over a permuted one; before a
#: program handed its successor its first group): mistral_l6 (32 KB a copy)
#: 142.7 / 142.2 / 141.1, 146.4; mistral_tp4_local (8 KB) 87.2 / 85.4 /
#: 67.8, 79.7; falconh1_l4 (16 KB) 413.1 / 418.2 / 382.6, 418.8;
#: kexaone_l5_full (32 KB) 588.6 / 595.2 / 557.2, 577.7; mimo_l7_full (16 KB
#: x 3: keys in two parts) 2,348.8 / 2,270.7 / 1,616.6, 2,133.8;
#: qwen3next_l8 (8 KB, one K/V head a call) 2,912.1 / 2,912.5 / 1,543.7,
#: 2,464.9; evabyte_l8 (128 KB, at its bytes) 1,355.4 / 1,353.0 / 1,352.1,
#: 1,361.2: the gain goes by how far a copy's ~0.045 us of issue exceeds its
#: bytes' time (32 KB at 819 GB/s: 0.04 us, the balance point). A stretch
#: that is NO run starts its 16-24 copies in line there too: as a loop the
#: permuted table read 142.3 / 90.7 / 441.9 / 601.6 / 2,429.4 / 2,984.2 (l6
#: 3 % better, every other cell 4-21 % worse), a table in runs the same
PAGE_RUN = 8


def _takes_runs(window: int, group: int, pages: int) -> bool:
    """Whether a walk in groups of ``group`` over a pool of ``pages`` moves
    stretches of ``PAGE_RUN`` neighbours a copy, decided where the call is
    traced: not a ring under a ``window`` (its tile starts at an unaligned
    page), whole stretches a group, a pool that holds one."""
    return bool(PAGE_RUN and not window and group % PAGE_RUN == 0
                and pages >= PAGE_RUN)


def _by_runs(copies, page_of, first, live, run: int):
    """``_page_walk``'s ``start`` and ``wait`` over aligned stretches of
    ``run`` pages: a stretch whose pages are all live and whose table entries
    are consecutive physical pages is started as ONE copy a pool
    (``copies(slot, j, src, run)``), any other a page at a time, as the walk
    without runs; then the group's live pages past its last whole stretch.
    A whole stretch is awaited as one copy however it was started: a
    semaphore counts bytes, and the wait takes its size from the copy."""

    def walk(g, slot, stretch_at, act):
        n, i0 = live(g), first(g)

        def page_at(j, _):
            for dma in copies(slot, j, page_of(i0 + j)):
                act(dma)

        jax.lax.fori_loop(
            0, n // run, lambda s, _: stretch_at(i0 + s * run, s * run), None)
        jax.lax.fori_loop(n // run * run, n, page_at, None)

    def start(g, slot):
        def stretch_at(i, j):  # logical pages i .. i + run - 1, the group's j-th on
            src = [page_of(i + k) for k in range(run)]

            def whole():
                for dma in copies(slot, j, src[0], run):
                    dma.start()

            def paged():
                for k in range(run):
                    for dma in copies(slot, j + k, src[k]):
                        dma.start()

            jax.lax.cond(functools.reduce(jnp.logical_and, (
                src[k] == src[0] + k for k in range(1, run))), whole, paged)

        walk(g, slot, stretch_at, lambda dma: dma.start())

    def wait(g, slot):
        def stretch_at(_, j):
            for dma in copies(slot, j, 0, run):
                dma.wait()

        walk(g, slot, stretch_at, lambda dma: dma.wait())

    return start, wait


def pages_in_runs(table, walked) -> int:
    """``_by_runs``'s test on the host (numpy, a server's counter): of the
    first ``walked[b]`` columns of each row of ``table``, the pages in whole
    aligned stretches of ``PAGE_RUN`` consecutive physical pages, which a
    walk of the row moves a stretch a copy."""
    import numpy as np

    table, run, whole = np.asarray(table), PAGE_RUN, np.asarray(walked) // PAGE_RUN
    # the stretches any row walks whole
    n = min(int(whole.max(initial=0)), table.shape[1] // run)
    laid = table[:, :n * run].reshape(len(table), n, run)
    side_by_side = (laid[..., 1:] - laid[..., :-1] == 1).all(-1)
    return run * int((side_by_side & (np.arange(n) < whole[:, None])).sum())


def _latent_kernel(layer_ref, off_ref, table_ref, ql_ref, qr_ref, *rest,
                   page: int, heads: int, tile_c: int, group: int, ring: int,
                   scale: float, window: int = 0, masked: bool = False):
    from jax.experimental.pallas import tpu as pltpu

    if masked:  # [tile_c, context] float32, > 0 where the query may attend
        allow_ref, *rest = rest
    c_hbm, r_hbm, o_ref, c_buf, r_buf, sems = rest
    bi = pl.program_id(0)
    ci = pl.program_id(1)
    rows, lat = ql_ref.shape
    width = group * page

    # folded row r is (chunk position ci*tile_c + r // heads, head r % heads)
    # at absolute position off + that; the walk ends at the page of the
    # tile's last query and starts at 0 or at the window's first page. The
    # table's width bounds it: queries padded past a chunk may sit past the
    # last column (a ring has no last column: it wraps)
    first = off_ref[bi] + ci * tile_c
    hi = (first + (tile_c - 1)) // page + 1
    if window:
        lo = _window_start(first, window, page)
    else:
        lo, hi = 0, jnp.minimum(hi, ring)
    layer = layer_ref[0]

    def copies(slot, j, src, n=None):
        # a slot is [group, page, width]: ``n`` pages side by side in the
        # pool are one stretch of it, and of the slot
        at = (lambda a: a) if n is None else (lambda a: pl.ds(a, n))
        return (pltpu.make_async_copy(c_hbm.at[layer, at(src)],
                                      c_buf.at[slot, at(j)], sems.at[0, slot]),
                pltpu.make_async_copy(r_hbm.at[layer, at(src)],
                                      r_buf.at[slot, at(j)], sems.at[1, slot]))

    # a ring's tile is one group of a window pool's pages: no runs there
    # (nor in a pool that holds less than one)
    runs = _takes_runs(window, group, c_hbm.shape[1])
    steps, start, arrive = _page_walk(
        copies, lambda i: table_ref[bi, i % ring if window else i], lo, hi, group,
        PAGE_RUN if runs else 0)

    @pl.when(jnp.logical_and(bi == 0, ci == 0))
    def _finite():
        # a group's dead pages keep what the slot held: rows of the pool or,
        # before the call's first copy, whatever VMEM held. Their columns
        # are masked, but a probability of 0 times a NaN is a NaN, and the
        # latent rows are the values too
        c_buf[...] = jnp.zeros_like(c_buf)

    start(0, 0)
    # the mask, but for the group's first position: ``base + c <= q_pos`` is
    # ``base <= ahead``, the window's lower bound likewise
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    ahead = first + r // heads - c
    dims = (((1,), (1,)), ((), ()))

    def body(g, acc):
        o, m, l = acc
        slot = arrive(g)
        kc = c_buf[slot].reshape(width, lat)              # whole sublane tiles
        kr = r_buf[slot].reshape(width, -1)               # [width, L], [width, R]
        scores = (jax.lax.dot_general(ql_ref[...], kc, dims,
                                      preferred_element_type=jnp.float32)
                  + jax.lax.dot_general(qr_ref[...], kr, dims,
                                        preferred_element_type=jnp.float32)
                  ) * scale                               # [rows, width]
        base = (lo + g * group) * page
        keep = base <= ahead
        if window:
            keep = keep & (base > ahead - window)
        if masked:
            allow = allow_ref[:, pl.ds(pl.multiple_of(g * width, width), width)]
            keep = keep & (jnp.broadcast_to(
                allow[:, None, :], (tile_c, heads, width)).reshape(rows, width) > 0.0)
        scores = jnp.where(keep, scores, _NEG)
        m_new = jnp.maximum(m, scores.max(axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        corr = jnp.exp(m - m_new)
        return (o * corr + jax.lax.dot_general(
            p.astype(kc.dtype), kc, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32),
            m_new, l * corr + p.sum(axis=-1, keepdims=True))

    # the sums ride the loop: in VMEM scratch a decode call cost 5-9 % more
    # and a 1,024-row chunk tile 4 % less on a v5e (PERF.md, PR 44)
    o, _, l = jax.lax.fori_loop(0, steps, body, (
        jnp.zeros((rows, lat), jnp.float32),
        jnp.full((rows, 1), _NEG, jnp.float32),
        jnp.zeros((rows, 1), jnp.float32)))
    # every query attends its own key at least (an indexer chooses among the
    # keys not after it and never none), in some step of its walk: what a
    # step without any key of a query summed meanwhile is scaled to 0 there,
    # and l is never truly zero
    o_ref[...] = (o / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("scale", "interpret", "window", "name"))
def mla_paged_attention(q_lat, q_rope, c_pages, r_pages, layer, page_table,
                        off, *, scale: float, interpret: bool = False,
                        window: int = 0, name: str = "mla_paged_attention",
                        allowed=None):
    """Absorbed latent attention straight from the latent page pools.

    q_lat: [B, C, H, L] (queries carried into the latent space), q_rope:
    [B, C, H, R]; c_pages: [layers, pages, page, L], r_pages: [layers,
    pages, page, R as held] (the WHOLE pools, left in HBM; the kernel copies
    ``pool[layer, page]``; a rope key is held in whole 128-lane rows, zeros
    behind it, and the queries' rope part is padded to that width here).
    Query i of row b sits at absolute position ``off[b] + i`` and attends
    keys 0..off+i with score ``(q_lat . c + q_rope . k_r) * scale``. Returns
    ``sum p c``: [B, C, H, L] in q_lat's dtype — the caller applies ``W_uv``.

    A (row, query tile) program walks its row's live pages itself, in
    groups, up to its last query's page: a table column past a row's
    context costs nothing.

    ``window`` > 0 (a sliding layer) bounds the keys below too — query at t
    attends ``t - window < s <= t`` — and ``page_table`` is then a RING:
    logical page i sits in column ``i % columns``. A (row, query tile) walks
    only the pages its window touches, from the one that holds its first
    query's oldest key. ``allowed`` [B, C, P * page] (> 0: attend) narrows
    each query's keys further — an indexer's choice —, the causal bound
    still applied. ``name`` names the kernel in a trace."""
    b, c, h, lat = q_lat.shape
    page, held = c_pages.shape[2], r_pages.shape[-1]
    table = jnp.asarray(page_table, jnp.int32)
    ring = table.shape[1]
    tile_c = latent_query_tile(c, h, lat)
    c_pad = -(-c // tile_c) * tile_c
    rows = tile_c * h
    group = _latent_group(tile_c, h, page, lat, held, c_pages.dtype.itemsize,
                          window)
    pad = ((0, 0), (0, c_pad - c), (0, 0))
    # padded queries sit past the chunk: finite garbage, sliced off below;
    # padded lanes meet a held key's zeros
    q_lat = jnp.pad(q_lat, (*pad, (0, 0)))
    q_rope = jnp.pad(q_rope, (*pad, (0, held - q_rope.shape[-1])))
    from jax.experimental.pallas import tpu as pltpu

    def _q_index(bi, ci, *_):
        return (bi, ci, 0)

    narrowed = []
    if allowed is not None:
        # whole groups: the walk's last reads its lanes past the context
        ctx = -(-ring // group) * group * page
        narrowed = [(jnp.pad(allowed.astype(jnp.float32), (
            *pad[:2], (0, ctx - allowed.shape[-1]))), pl.BlockSpec(
                (None, tile_c, ctx), _q_index))]
    kernel = functools.partial(
        _latent_kernel, page=page, heads=h, tile_c=tile_c, group=group,
        ring=ring, scale=float(scale), window=window,
        masked=allowed is not None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, c_pad // tile_c),
        in_specs=[
            pl.BlockSpec((None, rows, lat), _q_index),
            pl.BlockSpec((None, rows, held), _q_index),
            *[spec for _, spec in narrowed],
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, rows, lat), _q_index),
        scratch_shapes=[
            pltpu.VMEM((2, group, page, lat), c_pages.dtype),
            pltpu.VMEM((2, group, page, held), r_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    # programs run one after another on one core: the latent slots are
    # zeroed by the first and carry pool rows from then on (``_finite``)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c_pad * h, lat), q_lat.dtype),
        interpret=interpret,
        name=name,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * _walk_budget() + (8 << 20)),
    )(jnp.asarray(layer, jnp.int32).reshape(1), jnp.asarray(off, jnp.int32),
      table, q_lat.reshape(b, c_pad * h, lat),
      q_rope.reshape(b, c_pad * h, held), *[a for a, _ in narrowed],
      c_pages, r_pages)
    return out.reshape(b, c_pad, h, lat)[:, :c]


# -- the indexer's scores (learned sparse attention) ----------------------------
#
# A full layer with an indexer (DeepSeek-V3.2's lightning indexer) scores every
# cached token for every query: ``I(t, s) = sum_j w_j(t) relu(q_j(t) . k(s))``
# over ``Hi`` index heads, against ONE index key a token. The kernel walks the
# row's page table like ``mla_paged_attention`` (page groups of 128 keys, the
# pool whole with the layer scalar-prefetched) and writes the scores; who is
# chosen among them is ``ops/topk_select.dsa_topk_select``'s (a sort's before).


def _index_kernel(layer_ref, off_ref, table_ref, q_ref, w_ref, *rest,
                  page: int, heads: int, tile_c: int, group: int):
    k_refs, o_ref = rest[:group], rest[group]
    bi = pl.program_id(0)
    ci = pl.program_id(1)
    si = pl.program_id(2)
    cols = group * page
    max_pos = off_ref[bi] + ci * tile_c + (tile_c - 1)

    @pl.when(si * cols <= max_pos)
    def _score():
        kk = jnp.concatenate([r[...] for r in k_refs], axis=0)    # [cols, Di]
        dots = jax.lax.dot_general(q_ref[...], kk, (((1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        weighed = jnp.maximum(dots, 0.0) * w_ref[...]             # [rows, cols]
        o_ref[...] = weighed.reshape(tile_c, heads, cols).sum(axis=1)

    @pl.when(si * cols > max_pos)
    def _past():  # keys after every query of the tile: the caller masks them
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dsa_index_scores(q_i, w, index_pages, layer, page_table, off, *,
                     interpret: bool = False):
    """Index scores straight from the paged index-key pool.

    q_i: [B, C, Hi, Di] index queries, w: [B, C, Hi] float32 head weights;
    index_pages: [layers, pages, page, Di] (the WHOLE pool; ``layer`` picks
    the slice in the index maps). Query i of row b sits at ``off[b] + i``.
    Returns float32 [B, C, P * page]: ``sum_j w_j relu(q_j . k)`` for every
    key of the row's table (bfloat16 operands, float32 accumulation); keys
    in groups past a tile's last query read 0 — the caller masks by
    position anyway."""
    b, c, h, di = q_i.shape
    page = index_pages.shape[2]
    group = _LATENT_GROUP
    table = jnp.asarray(page_table, jnp.int32)
    ctx = table.shape[1] * page
    steps = -(-table.shape[1] // group)
    if steps * group != table.shape[1]:
        table = jnp.pad(table, ((0, 0), (0, steps * group - table.shape[1])))
    tile_c = c if c * h <= 512 or c % 8 else 8
    from jax.experimental.pallas import tpu as pltpu

    def _q_index(bi, ci, si, *_):
        return (bi, ci, 0)

    def _page_index(g):
        def index(bi, ci, si, layer_ref, off_ref, table_ref):
            pi = si * group + g
            max_pos = off_ref[bi] + ci * tile_c + (tile_c - 1)
            return (layer_ref[0],
                    jnp.where(pi * page <= max_pos, table_ref[bi, pi], 0),
                    0, 0)
        return index

    rows = tile_c * h
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, c // tile_c, steps),
        in_specs=[
            pl.BlockSpec((None, rows, di), _q_index),
            pl.BlockSpec((None, rows, 1), _q_index),
            *[pl.BlockSpec((None, None, page, di), _page_index(g))
              for g in range(group)],
        ],
        out_specs=pl.BlockSpec((None, tile_c, group * page),
                               lambda bi, ci, si, *_: (bi, ci, si)),
    )
    out = pl.pallas_call(
        functools.partial(_index_kernel, page=page, heads=h, tile_c=tile_c,
                          group=group),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, c, steps * group * page), jnp.float32),
        interpret=interpret,
        name="dsa_index_topk_scores",
    )(jnp.asarray(layer, jnp.int32).reshape(1), jnp.asarray(off, jnp.int32),
      table, q_i.astype(jnp.bfloat16).reshape(b, c * h, di),
      w.astype(jnp.float32).reshape(b, c * h, 1), *[index_pages] * group)
    return out[..., :ctx]
