"""Compile the main path's Pallas kernels for a DESCRIBED TPU v5e.

Interpret mode (every other kernel test) checks the arithmetic and none of
what the chip's compiler checks: block shapes against the (8, 128) tiling,
slices against lane alignment, scratch against the fast-memory limit. The
TPU compiler is installed with jax and compiles for a chip that is
described and not attached, so these cases ask it directly — at the widths
the main path serves (BERT-base, Llama-3-8B head geometry, the decoder
default) — and guard every later change at no chip time. Nothing runs: a
compile that passes is not a chip run and says nothing about results or
speed. Skipped only where the topology cannot be described.
"""

from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from arkflow_tpu.ops.ragged_attention import (
    _page_group,
    paged_flash_attention,
    ragged_flash_attention,
)
from arkflow_tpu.ops.segment_attention import segment_flash_attention

BF16 = jnp.bfloat16
I32 = jnp.int32


@pytest.fixture(scope="module")
def v5e():
    """The four described devices of a v5e 2x2 host, compile cache off: a
    described-chip executable is written to the persistent cache but cannot
    be read back without a chip, and the next compile would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, devices, *shapes, shardings=None):
    """Lower + compile ``fn`` for the described chip(s); raises what the
    chip's compiler would raise. ``shapes`` are (shape, dtype) pairs."""
    if shardings is None:
        shardings = [SingleDeviceSharding(devices[0])] * len(shapes)
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh)
            for (s, d), sh in zip(shapes, shardings)]
    return jax.jit(fn).lower(*args).compile()


# -- ragged (auto-selected for unpacked BERT buckets of seq >= 128) ----------

BERT_B, BERT_H, BERT_DH = 64, 12, 64


@pytest.mark.parametrize("seq", [32, 128])
def test_ragged_flash_compiles_bert_base(v5e, seq):
    qkv = ((BERT_B, BERT_H, seq, BERT_DH), BF16)
    compiled = _compile(
        lambda q, k, v, n: ragged_flash_attention(
            q, k, v, n, tile_q=min(seq, 128), tile_k=min(seq, 128)),
        v5e, qkv, qkv, qkv, ((BERT_B,), I32))
    assert "tpu_custom_call" in compiled.as_text()


# -- segment (packed BERT, opt-in) -------------------------------------------


def _segment_compiled(devices, seq):
    qkv = ((BERT_B, BERT_H, seq, BERT_DH), BF16)
    return _compile(
        lambda q, k, v, seg: segment_flash_attention(
            q, k, v, seg, tile_q=min(seq, 128), tile_k=min(seq, 128)),
        devices, qkv, qkv, qkv, ((BERT_B, seq), I32))


@pytest.mark.parametrize("seq", [32, 128])
def test_segment_flash_compiles_bert_base(v5e, seq):
    _segment_compiled(v5e, seq)


def test_segment_flash_is_a_mosaic_kernel(v5e):
    """The repair kept the kernel a kernel: the compiled module calls
    Mosaic, it did not fall to an XLA rewrite."""
    assert "tpu_custom_call" in _segment_compiled(v5e, 512).as_text()


# -- paged (auto-selected decode + chunked-prefill kernel on a TPU) -----------

#: (kv_heads, heads, dh): Llama-3-8B's head geometry, the decoder default's,
#: Llama-3.2-1B's, Phi-3-mini's and Gemma-7B's
GEOMETRIES = {"llama3_8b": (8, 32, 128), "decoder_default": (4, 8, 32),
              "llama32_1b": (8, 32, 64), "phi3_mini": (32, 32, 96),
              "gemma_7b": (16, 16, 256)}
SLOTS, PAGE, PAGES_PER, POOL_PAGES, POOL_LAYERS = 8, 16, 32, 257, 4


def _paged_shapes(geometry: str, chunk: int):
    """q, the WHOLE pools, the layer, the page table, the offsets."""
    kvh, h, dh = GEOMETRIES[geometry]
    # a head narrower than 128 lanes: row-major pools (``cache_spec``)
    pool = ((POOL_LAYERS, POOL_PAGES, PAGE, *(
        (kvh * dh,) if dh < 128 else (kvh, dh))), BF16)
    return (((SLOTS, chunk, h, dh), BF16), pool, pool, ((), I32),
            ((SLOTS, PAGES_PER), I32), ((SLOTS,), I32))


@pytest.mark.parametrize("chunk", [1, 128], ids=["decode", "chunk128"])
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_paged_flash_compiles(v5e, geometry, chunk):
    """Every head size compiles, by the kernel's own walk: a head of a
    multiple of 128 lanes from pools [.., kv heads, width]; a narrower one
    (32, 64, 96) from row-major pools, a token's heads whole runs of
    lcm(width, 128) lanes. (Out of pools [.., kv heads, width < 128] the
    chip's compiler takes no copy of a page — "Slice shape along dimension
    2 must be aligned to tiling (128)" —: the call refuses them by name.)"""
    compiled = _compile(paged_flash_attention, v5e, *_paged_shapes(geometry, chunk))
    assert "tpu_custom_call" in compiled.as_text()
    kvh, _, dh = GEOMETRIES[geometry]
    if dh % 128:
        shapes = list(_paged_shapes(geometry, chunk))
        shapes[1] = shapes[2] = ((POOL_LAYERS, POOL_PAGES, PAGE, kvh, dh), BF16)
        with pytest.raises(ValueError, match="row-major: cache_spec"):
            _compile(paged_flash_attention, v5e, *shapes)


#: the narrow-head walk as ``lfm2_l12`` serves it: 32 / 8 heads of 64 (four
#: 128-lane runs a token), 128 lanes of a 288-column table, a chunk of 256
NARROW = {"decode": (128, 1), "chunk": (1, 256)}


@pytest.mark.parametrize("step", list(NARROW))
def test_narrow_head_walk_compiles_as_served(v5e, step):
    """LFM2's attention layers: the row-major pools (3 layers x 1.2 GB of
    pages) stay arguments of the Mosaic call — no temporary: nothing is
    re-laid around it —, and a head of 64 under a 41-column ring compiles
    too (no cell serves one; the CPU tests do)."""
    b, c = NARROW[step]
    pool = ((3, 1 + 128 * 288 // 4, PAGE, 8 * 64), BF16)
    args = (((b, c, 32, 64), BF16), pool, pool, ((), I32), ((b, 288), I32),
            ((b,), I32))
    compiled = _compile(paged_flash_attention, v5e, *args)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 1024 * 1024
    ring = list(args)
    ring[4] = ((b, 41), I32)
    _compile(lambda *a: paged_flash_attention(*a, window=128), v5e, *ring)


#: the walk as the benchmark's cells serve it: (lanes, table columns, heads,
#: kv heads, the chunk's length); the pools hold every lane's columns once
SERVED = {"mistral_l6": (16, 136, 32, 8, 128), "mistral_tp4_local": (16, 136, 8, 2, 128),
          "falconh1_l4": (128, 64, 20, 4, 256), "kexaone_l5_full": (48, 528, 64, 8, 512)}


@pytest.mark.parametrize("step", ["decode", "chunk"])
@pytest.mark.parametrize("cell", list(SERVED))
def test_paged_flash_compiles_as_served(v5e, cell, step):
    """The four geometries whose steps the kernel's walk carries (tp4's as
    one chip sees it inside ``shard_map``: a quarter of the heads), a decode
    step over every lane and one row's chunk. The window layers' ring of 41
    columns: ``test_paged_window_attention_compiles``."""
    lanes, table, h, kvh, chunk = SERVED[cell]
    b, c = (lanes, 1) if step == "decode" else (1, chunk)
    pool = ((2, 1 + lanes * table, PAGE, kvh, 128), BF16)
    compiled = _compile(paged_flash_attention, v5e, ((b, c, h, 128), BF16),
                        pool, pool, ((), I32), ((b, table), I32), ((b,), I32))
    assert "tpu_custom_call" in compiled.as_text()
    # many pages a step of the walk under a decode step's few rows, few
    # under a chunk tile's ~1,024
    rows = c * h if c * h <= 1024 else 1024 // h // 8 * 8 * h
    assert 1 <= _page_group(rows, PAGE, kvh, 128, 2) <= _page_group(h, PAGE, kvh, 128, 2)


#: a prefill chunk as each per-head cell serves it, one chip's share: (query
#: heads, K/V heads, key width, table columns, chunk, window, sink)
CHUNKS = {"mistral_l6": (32, 8, 128, 136, 128, 0, False),
          "mistral_tp4_local": (8, 2, 128, 136, 128, 0, False),
          "falconh1_l4": (20, 4, 128, 64, 256, 0, False),
          "kexaone_l5_full": (64, 8, 128, 528, 512, 0, False),
          "kexaone_l5_window": (64, 8, 128, 41, 512, 128, False),
          "mimo_l7_full_key_parts": (64, 4, 192, 832, 512, 0, False),
          "mimo_l7_window_sink": (64, 8, 192, 41, 512, 128, True)}


@pytest.mark.parametrize("cell", list(CHUNKS))
def test_a_chunks_per_head_product_compiles_inside_its_vmem_limit(v5e, cell):
    """Every per-head cell's chunk takes the K/V-head-at-a-time cut (the
    kernel's own predicate on the call's shapes), what its program holds in
    VMEM — two slots of a group's pages (and no copy of them: every cell's
    slot is one 128-lane run a part), the accumulators, the query and output
    blocks twice — is inside the limit the call asks for, and the chip's
    compiler takes its strided reads of the slots' 32-bit words."""
    import math

    from arkflow_tpu.ops import ragged_attention as ra

    h, kvh, dk, table, chunk, window, sink = CHUNKS[cell]
    parts = 1 if dk % 128 == 0 else -(-dk // 128)
    held = dk // parts if dk % 128 == 0 else 128
    tile_c = ra.query_tile(chunk, h)
    assert ra.per_kv_head(tile_c, h, kvh) and ra.kernel_walks(parts * held, 128, False)
    rows = tile_c * h
    _, spec, params = ra._walk_call(
        1, chunk // tile_c, rows, parts * held, jnp.dtype(BF16), page=PAGE, kvh=kvh,
        heads=h, tile_c=tile_c, ring=table, window=window, dv=128, scale=dk ** -0.5,
        sink=sink, parts=parts, part_stride=2 * 257)
    limit = params["compiler_params"].vmem_limit_bytes
    scratch = sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
                  for s in spec.scratch_shapes if "sem" not in str(s.dtype))
    blocks = 2 * rows * (parts * held + 128) * 2
    assert scratch + blocks < limit <= 64 << 20
    pages = 1 + 16 * table
    compiled = _compile(
        lambda q, kp, vp, layer, t, off, s: paged_flash_attention(
            q, kp, vp, layer, t, off, window=window, sink=s if sink else None),
        v5e, ((1, chunk, h, dk), BF16), ((2 * parts, pages, PAGE, kvh, held), BF16),
        ((2, pages, PAGE, kvh, 128), BF16), ((), I32), ((1, table), I32),
        ((1,), I32), ((h,), jnp.float32))
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 1024 * 1024


#: the per-head walk in runs (PR 61) at the widths whose copies were the
#: smallest: (lanes, table columns, query heads, K/V heads, key width, value
#: width, chunk) — l6's, tp4's shard's, ``mimo_l7``'s full layers' (keys of 192
#: in two parts) and ``qwen3next_l8``'s (one K/V head of 256 a call)
RUN_WALKS = {"mistral_l6": (16, 136, 32, 8, 128, 128, 128),
             "mistral_tp4_local": (16, 136, 8, 2, 128, 128, 128),
             "mimo_l7_full": (64, 832, 64, 4, 192, 128, 512),
             "qwen3next_l8": (128, 576, 8, 1, 256, 256, 512)}


@pytest.mark.parametrize("step", ["decode", "chunk"])
@pytest.mark.parametrize("cell", list(RUN_WALKS))
def test_the_per_head_walk_in_runs_compiles(v5e, cell, step):
    """A stretch of ``PAGE_RUN`` neighbours as ONE copy a pool and a key part
    out of pools that ride as flat rows, and a program's last step starting
    the next program's first group: the chip's compiler takes both at a
    decode tile and a chunk tile, the group whole runs, nothing re-laid."""
    from arkflow_tpu.ops import ragged_attention as ra

    lanes, table, h, kvh, dk, dv, chunk = RUN_WALKS[cell]
    parts = 1 if dk % 128 == 0 else -(-dk // 128)
    held = dk // parts if dk % 128 == 0 else 128
    b, c = (lanes, 1) if step == "decode" else (1, chunk)
    tile_c = ra.query_tile(c, h)
    group = _page_group(tile_c * h, PAGE, kvh, parts * held, 2, dv,
                        ra.per_kv_head(tile_c, h, kvh), parts)
    pages = 1 + lanes * table // 4
    assert ra.PAGE_RUN == 8 and ra._takes_runs(0, group, pages)
    compiled = _compile(
        paged_flash_attention, v5e, ((b, c, h, dk), BF16),
        ((2 * parts, pages, PAGE, kvh, held), BF16), ((2, pages, PAGE, kvh, dv), BF16),
        ((), I32), ((b, table), I32), ((b,), I32))
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 1024 * 1024


def test_paged_flash_is_a_mosaic_kernel(v5e):
    compiled = _compile(paged_flash_attention, v5e,
                        *_paged_shapes("llama3_8b", 1))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("chunk", [1, 128], ids=["decode", "chunk128"])
def test_paged_flash_compiles_under_tp4_shard_map(v5e, chunk):
    """The serving wrapper (models/paged_decode._attend_paged): pools
    sharded over kv heads on a 4-device ``tp`` mesh, the kernel per shard
    under ``shard_map`` — and no collective, since attention is independent
    per kv head."""
    from arkflow_tpu.models.decoder import llama3_8b
    from arkflow_tpu.models.paged_decode import _attend_paged
    from arkflow_tpu.parallel.mesh import kv_pool_sharding

    import numpy as np

    mesh = Mesh(np.asarray(v5e).reshape(4), ("tp",))
    kv = kv_pool_sharding(mesh)
    heads = NamedSharding(mesh, P(None, None, "tp", None))
    repl = NamedSharding(mesh, P())
    cfg = llama3_8b()

    def attend(q, kp, vp, layer, table, off):
        return _attend_paged(q, kp, vp, layer, table, off, cfg, kv, False)

    compiled = _compile(attend, v5e, *_paged_shapes("llama3_8b", chunk),
                        shardings=[heads, kv, kv, repl, repl, repl])
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-gather" not in text and "all-reduce" not in text


# -- the whole dense steps (Mistral-7B widths, the benchmark's pools) ----------

#: 16 slots x 136 pages of 16 tokens + the scratch page, as both Mistral cells
MISTRAL_SLOTS, MISTRAL_PAGES, MISTRAL_TABLE = 16, 2177, 136
#: one layer's slice of ONE pool is 71.3 MB: a single surviving copy fails
POOL_TEMP_LIMIT = 64 * 1024 * 1024


def _dense_steps(devices, layers: int, tp: int, fused: bool = False):
    """The dense ``_decode`` (16 lanes) and ``_chunk`` (1 x 128) programs —
    or, ``fused``, the ONE program that carries both (``_fused``) — as the
    server jits them — pools donated, under ``tp`` the server's in / out
    shardings — compiled for the described chip(s) at Mistral-7B widths."""
    import numpy as np

    from arkflow_tpu.models import decoder as dec
    from arkflow_tpu.models.paged_decode import (init_page_pool, paged_decode_step,
                                                 paged_fused_step,
                                                 paged_prefill_chunk)
    from arkflow_tpu.parallel.mesh import kv_pool_sharding

    cfg = dec.DecoderConfig(vocab_size=32768, dim=4096, layers=layers, heads=32,
                            kv_heads=8, ffn=14336, max_seq=32768,
                            rope_theta=1e6, norm_eps=1e-5)
    params = jax.tree_util.tree_map(
        lambda a, dtype: jax.ShapeDtypeStruct(a.shape, dtype),
        jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg)),
        dec.serve_dtypes(cfg))               # as the server places them
    pool, _ = jax.eval_shape(
        lambda: init_page_pool(cfg, MISTRAL_PAGES, PAGE))
    kv = None
    if tp > 1:
        mesh = Mesh(np.asarray(devices).reshape(tp), ("tp",))
        kv, repl = kv_pool_sharding(mesh), NamedSharding(mesh, P())
        place = jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec),
            dec.param_specs(cfg, {"tp": "tp"}), is_leaf=lambda x: isinstance(x, P))
    else:
        repl = SingleDeviceSharding(devices[0])
        place = jax.tree_util.tree_map(lambda _: repl, params)
    kern = dict(kv_sharding=kv, attention_kernel="paged")

    def decode(p, tok, lens, act, table, kp, vp):
        return paged_decode_step(p, cfg, tok, lens, act, table, kp, vp,
                                 return_logits=True, **kern)

    def chunk(p, ids, off, clen, table, kp, vp):
        return paged_prefill_chunk(p, cfg, ids, off, clen, table, kp, vp, **kern)

    def both(p, tok, lens, act, table, ids, off, clen, its_table, kp, vp):
        return paged_fused_step(p, cfg, tok, lens, act, table, ids, off, clen,
                                its_table, kp, vp, return_logits=True, **kern)

    def compiled(fn, *operands):
        def struct(a, sharding):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)

        pools = kv or repl
        n = len(operands)
        return jax.jit(fn, donate_argnums=(n + 1, n + 2),
                       out_shardings=(repl, pools, pools)).lower(
            jax.tree_util.tree_map(struct, params, place),
            *[jax.ShapeDtypeStruct(s, d, sharding=repl) for s, d in operands],
            struct(pool, pools), struct(pool, pools)).compile()

    s = MISTRAL_SLOTS
    lanes = (((s,), I32), ((s,), I32), ((s,), jnp.bool_), ((s, MISTRAL_TABLE), I32))
    prompt = (((1, 128), I32), ((1,), I32), ((1,), I32), ((1, MISTRAL_TABLE), I32))
    if fused:
        return (compiled(both, *lanes, *prompt),)
    return compiled(decode, *lanes), compiled(chunk, *prompt)


def _collectives(text: str) -> dict:
    """The collectives of a compiled module by kind, each with its result
    shape (the text before ``kind(``)."""
    import re

    found = {}
    for line in text.splitlines():
        m = re.search(r"= (.*?) (all-gather|all-reduce|all-to-all|"
                      r"collective-permute|reduce-scatter)(-start)?\(", line)
        if m:
            found.setdefault(m.group(2), []).append(m.group(1))
    return found


@pytest.mark.parametrize("fused", [False, True], ids=["in-turn", "fused"])
@pytest.mark.parametrize("layers,tp", [(6, 1), (16, 4)], ids=["l6", "tp4"])
def test_dense_steps_carry_the_pools_whole(v5e, layers, tp, fused):
    """No layer's pool slice is copied out, scattered into and written back,
    and the pools are not copied once a step: the programs need no temporary
    the size of a slice. Under tp the collectives are the layer's own (two
    all-gathers, three all-reduces in the loop body's text) and none moves
    a pool. So too the ONE program of a decode step that carries a chunk
    (144 rows through the weights, the kernel called twice a layer)."""
    for step in _dense_steps(v5e, layers, tp, fused):
        text = step.as_text()
        assert "tpu_custom_call" in text
        assert step.memory_analysis().temp_size_in_bytes < POOL_TEMP_LIMIT
        found = _collectives(text)
        if tp == 1:
            assert not found
            continue
        assert set(found) <= {"all-gather", "all-reduce"}
        assert len(found.get("all-gather", ())) <= 2
        assert len(found.get("all-reduce", ())) <= 3
        pool_dims = f"{MISTRAL_PAGES},{PAGE},"
        assert not any(pool_dims in shape for shapes in found.values()
                       for shape in shapes)


# -- latent (MLA) paged attention and the expert product (Kanana-2 widths) ----

KANANA_HEADS, KANANA_LATENT, KANANA_ROPE = 32, 512, 64
KANANA_EXPERTS, KANANA_DIM, KANANA_WIDTH = 130, 2048, 768  # 128 routed + 2 shared
#: a latent model's rope keys as its pools hold them (``cache_spec``): whole
#: 128-lane rows, which the kernel's own walk can copy a page of
ROPE_HELD = 128
#: no copy of a pool (0.2 GB and up) survives around a latent kernel's call:
#: the queries' rope part padded to the held width and the indexer's choice
#: padded to whole groups are its largest temporaries
LATENT_TEMP_LIMIT = 64 * 1024 * 1024


def _latent_compiled(v5e, rows, chunk, heads, latent, pool, table, rope_held=ROPE_HELD,
                     masked=False, **kw):
    """``mla_paged_attention`` as served, compiled: the kernel walks the
    table itself, its group's two slots, score tiles and carried sums inside
    the VMEM limit the call asks for (the chip's compiler refuses a kernel
    that is not)."""
    from arkflow_tpu.ops.ragged_attention import mla_paged_attention

    layers, pages = pool
    allowed = [((rows, chunk, table * 16), jnp.float32)] if masked else []
    return _compile(
        lambda ql, qr, cp, rp, layer, table, off, *allowed: mla_paged_attention(
            ql, qr, cp, rp, layer, table, off, allowed=(allowed or (None,))[0], **kw),
        v5e, ((rows, chunk, heads, latent), BF16), ((rows, chunk, heads, 64), BF16),
        ((layers, pages, 16, latent), BF16), ((layers, pages, 16, rope_held), BF16),
        ((), I32), ((rows, table), I32), ((rows,), I32), *allowed)


@pytest.mark.parametrize("rows,chunk", [(16, 1), (1, 128)], ids=["decode", "chunk128"])
def test_mla_paged_attention_compiles(v5e, rows, chunk):
    compiled = _latent_compiled(v5e, rows, chunk, KANANA_HEADS, KANANA_LATENT,
                                (6, 2177), 136, scale=192 ** -0.5)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < LATENT_TEMP_LIMIT


@pytest.mark.parametrize("rows,chunk", [(32, 1), (1, 512)], ids=["decode", "chunk512"])
def test_the_latent_walk_in_runs_compiles_at_xing4_widths(v5e, rows, chunk):
    """The walk that moves a stretch of ``PAGE_RUN`` neighbours as one copy
    (PR 54), at the cell it was written for: 32 lanes over 992 columns, a
    512-token chunk in tiles of 32 positions; the slots [group, page, width]
    are read back as whole sublane tiles."""
    from arkflow_tpu.ops import ragged_attention as ra

    assert ra._latent_group(ra.latent_query_tile(chunk, 32, 512), 32, 16, 512,
                            ROPE_HELD, 2) % ra.PAGE_RUN == 0
    compiled = _latent_compiled(v5e, rows, chunk, 32, 512, (10, 1 + 32 * 992), 992,
                                scale=192 ** -0.5)
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < LATENT_TEMP_LIMIT


def test_latent_rope_keys_of_64_lanes_are_not_walked(v5e):
    """Why the rope keys are held in whole 128-lane rows: the kernel's own
    copy of a page out of a 64-lane pool is refused (Mosaic sees the pool
    padded to 128 lanes and slices it in whole rows only), out of the held
    pool it compiles (the case above)."""
    with pytest.raises(Exception, match="aligned to tiling"):
        _latent_compiled(v5e, 16, 1, KANANA_HEADS, KANANA_LATENT, (6, 2177), 136,
                         rope_held=KANANA_ROPE, scale=192 ** -0.5)


def _expert_product_compiled(v5e, tokens, layers, experts, dim, width):
    """``moe_expert_swiglu`` over a stack of ``layers``, compiled, and the
    bytes of ONE layer's experts. A call of up to 128 rows compiles the
    one-tile kernel, more rows the grouped one (``ops/moe_grouped.py``)."""
    from arkflow_tpu.ops.moe_experts import moe_expert_swiglu

    up = ((layers, experts, dim, width), BF16)
    compiled = _compile(
        lambda x, cw, wg, wu, wd, layer: moe_expert_swiglu(x, cw, wg, wu, wd, layer),
        v5e, ((tokens, dim), BF16), ((tokens, experts), jnp.float32),
        up, up, ((layers, experts, width, dim), BF16), ((), I32))
    return compiled, 3 * experts * dim * width * 2


def _no_copy_of_a_layer(compiled, layer_bytes, grouped):
    """The stack rides whole: the program holds no temporary anywhere near
    one layer's experts. The bound is a SHARE of that layer (a sixteenth:
    26-80 MB at the widths served; it was a flat 64 MB while every call was
    one tile), since a grouped product may hold temporaries that grow with
    the call — this one's are the ``rank`` table and its transposes, well
    under 1 MB: no (row, expert) pair is ever written to HBM."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("moe_expert_grouped" in text) == grouped
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes // 16


@pytest.mark.parametrize("tokens", [16, 128, 300], ids=["decode", "chunk128", "tiled"])
def test_moe_expert_swiglu_compiles(v5e, tokens):
    """The stacked experts ride whole (5 layers), so no layer's 1.2 GB is
    copied out for the kernel: the program needs no temporary of that size.
    300 rows compile the grouped kernel (three row tiles of a group)."""
    compiled, layer_bytes = _expert_product_compiled(
        v5e, tokens, 5, KANANA_EXPERTS, KANANA_DIM, KANANA_WIDTH)
    _no_copy_of_a_layer(compiled, layer_bytes, grouped=tokens > 128)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024


@pytest.mark.parametrize("tokens,layers,experts,dim,width", [
    (512, 4, 17, 6144, 2048), (512, 6, 16, 4096, 2048), (512, 8, 65, 2048, 512),
    (256, 10, 32, 2048, 1792), (384, 10, 32, 2048, 1792)],
    ids=["kexaone512", "mimo512", "qwen3next512", "lfm2_256", "lfm2_fused384"])
def test_grouped_expert_product_compiles(v5e, tokens, layers, experts, dim, width):
    """A chunk's grouped product at the four other routed cells' widths and
    expert layers (K-EXAONE 16 + 1 of 6,144 x 2,048, MiMo 16 of 4,096 x
    2,048, Qwen3-Next 64 + 1 of 2,048 x 512, LFM2 32 of 2,048 x 1,792 at
    its 256-row chunk and at its fused step's block of 128 lanes + 256 rows):
    the rows, their float32 sums, a group's two
    scratches and the weights' slices inside the fast-memory limit the call
    asks for, and no copy of a layer."""
    compiled, layer_bytes = _expert_product_compiled(
        v5e, tokens, layers, experts, dim, width)
    _no_copy_of_a_layer(compiled, layer_bytes, grouped=True)


def test_a_latent_fused_step_compiles_at_kanana2_widths(v5e):
    """``kanana2_l6``'s ``_fused`` program — 16 lanes and a 128-token chunk,
    ONE block of 144 rows through six layers at the published widths, the
    pools donated — compiled for the described chip: the latent kernel twice
    a layer (the two steps' own calls), the expert product ONCE a layer and
    grouped (144 rows are above one token tile), no pool and no layer's
    experts copied, and the counters by row range beside the logits."""
    from arkflow_tpu.models import decoder as dec
    from arkflow_tpu.models.paged_decode import init_page_pool, paged_fused_step

    cfg = dec.DecoderConfig(
        vocab_size=128256, dim=KANANA_DIM, layers=6, heads=KANANA_HEADS, ffn=6144,
        max_seq=32768, rope_theta=1e6, norm_eps=1e-6, kv_lora_rank=KANANA_LATENT,
        qk_nope_head_dim=128, qk_rope_head_dim=KANANA_ROPE, v_head_dim=128,
        rope_interleave=True, n_routed_experts=128, num_experts_per_tok=6,
        n_shared_experts=2, moe_intermediate_size=KANANA_WIDTH,
        first_k_dense_replace=1, routed_scaling_factor=2.448)
    chip = SingleDeviceSharding(v5e[0])

    def struct(a, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        struct, jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg)),
        dec.serve_dtypes(cfg))               # as the server places them
    kp, vp = jax.tree_util.tree_map(struct, jax.eval_shape(
        lambda: init_page_pool(cfg, MISTRAL_PAGES, PAGE)))
    s, table = MISTRAL_SLOTS, MISTRAL_TABLE
    operands = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip) for shape, dtype in (
        ((s,), I32), ((s,), I32), ((s,), jnp.bool_), ((s, table), I32),
        ((1, 128), I32), ((1,), I32), ((1,), I32), ((1, table), I32))]
    def step(p, *a):
        return paged_fused_step(p, cfg, *a, return_logits=True,
                                attention_kernel="paged")

    compiled = jax.jit(step, donate_argnums=(9, 10)).lower(
        params, *operands, kp, vp).compile()
    text = compiled.as_text()
    assert "moe_expert_grouped" in text and "mla_paged_attention" in text
    logits, _, _, counters = jax.eval_shape(step, params, *operands, kp, vp)
    assert logits.shape == (s + 1, 128256) and counters.shape == (3, 3)
    # a pool is 0.2 GB, a layer's experts 1.2 GB: neither is copied
    assert compiled.memory_analysis().temp_size_in_bytes < LATENT_TEMP_LIMIT


# -- a layer pattern's kernels (dots3-note-prev widths, page 16, chunk 512) ---

DOTS_DIM, DOTS_WIDTH, DOTS_HELD = 5120, 1536, 33      # 32 held + 1 shared
DOTS_PAGES = 784                                      # 12,544 tokens a slot
STEPS = pytest.mark.parametrize("rows,chunk", [(32, 1), (1, 512)],
                                ids=["decode", "chunk512"])


@STEPS
def test_swa_latent_attention_compiles(v5e, rows, chunk):
    """The sliding layers' window attention: 64 heads, latent 1,024, the
    lower bound (513) over a ring of 65 window pages, a tile's whole walk
    one group."""
    compiled = _latent_compiled(v5e, rows, chunk, 64, 1024, (3, 2081), 65,
                                scale=256 ** -0.5, window=513,
                                name="swa_latent_attention")
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "swa_latent_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < LATENT_TEMP_LIMIT


@pytest.mark.parametrize("rows,chunk", [(32, 1), (1, 64)], ids=["decode", "tile64"])
def test_dsa_index_scores_compiles(v5e, rows, chunk):
    """The indexer's scores: 64 index heads of 128 against every index key
    of a 12,544-token table."""
    from arkflow_tpu.ops.ragged_attention import dsa_index_scores

    compiled = _compile(
        lambda q, w, ip, layer, table, off: dsa_index_scores(
            q, w, ip, layer, table, off),
        v5e, ((rows, chunk, 64, 128), jnp.float32), ((rows, chunk, 64), jnp.float32),
        ((2, 25089, 16, 128), BF16), ((), I32), ((rows, DOTS_PAGES), I32),
        ((rows,), I32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "dsa_index_topk_scores" in text


@pytest.mark.parametrize("rows,chunk", [(32, 1), (1, 64)], ids=["decode", "tile64"])
def test_dsa_topk_select_compiles(v5e, rows, chunk):
    """The indexer's choice of 2,048 among a 12,544-token table's scores: 32
    rows a block (1.6 MB each of scores, integer keys and mask in VMEM, the
    first and the last double-buffered), int32 compares, shifts and row
    counts, a scalar maximum of the block's positions."""
    from arkflow_tpu.ops.topk_select import dsa_topk_select

    compiled = _compile(
        lambda s, positions: dsa_topk_select(s, positions, k=2048),
        v5e, ((rows, chunk, 16 * DOTS_PAGES), jnp.float32), ((rows, chunk), I32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "dsa_topk_select" in text
    assert "sort(" not in text
    # the call's operands and result as they are: no padded copy at these shapes
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 * 1024


@pytest.mark.parametrize("tokens", [32, 512], ids=["decode", "chunk512"])
def test_moe_expert_swiglu_compiles_at_the_held_share(v5e, tokens):
    """32 held + 1 shared experts of hidden 5,120 x 1,536, three sliding
    layers stacked (3.1 GB a matrix kind... a third each): no copy of a
    layer. The 512-row chunk compiles the grouped kernel."""
    compiled, layer_bytes = _expert_product_compiled(
        v5e, tokens, 3, DOTS_HELD, DOTS_DIM, DOTS_WIDTH)
    _no_copy_of_a_layer(compiled, layer_bytes, grouped=tokens > 128)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024


@pytest.mark.parametrize("pages", [DOTS_PAGES, 2048], ids=["12k", "32k"])
@STEPS
def test_dsa_sparse_attention_compiles(v5e, rows, chunk, pages):
    """The indexed layers' attention: the latent kernel in place under the
    indexer's choice (a float32 mask over the slot's table), 128 heads; at
    the cell's 12,544-token table and at a 32,768-token one (the one form
    serves every context: the choice rides as one [tile_c, context] block a
    program, 1 MB a chunk tile at 32k)."""
    compiled = _latent_compiled(v5e, rows, chunk, 128, 512, (2, 32 * pages + 1),
                                pages, masked=True, scale=192 ** -0.5,
                                name="dsa_sparse_attention")
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "dsa_sparse_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < (
        LATENT_TEMP_LIMIT if pages == DOTS_PAGES else 3 * LATENT_TEMP_LIMIT)


# -- the recurrent-state kind (Falcon-H1-34B widths, 128 slots, chunk 256) ----

FALCON_SLOTS, FALCON_TABLE, FALCON_CHUNK = 128, 64, 256
#: one layer's slab of the state pool is 129 x 4 MiB = 541 MB: a single
#: surviving copy of it (or of the 2.16 GB pool) fails
STATE_TEMP_LIMIT = 256 * 1024 * 1024


def _falcon_cfg(layers: int = 4):
    from arkflow_tpu.models import decoder as dec

    return dec.DecoderConfig(
        vocab_size=261120, dim=5120, layers=layers, heads=20, kv_heads=4,
        head_dim=128, ffn=21504, max_seq=262144, rope_theta=100000000000,
        norm_eps=1e-5, mamba_d_ssm=4096, mamba_n_heads=32, mamba_d_head=128,
        mamba_d_state=256, mamba_n_groups=2, mamba_d_conv=4,
        mamba_chunk_size=128, embedding_multiplier=5.656854249492381,
        attention_out_multiplier=0.0375, key_multiplier=0.011048543456039804,
        lm_head_multiplier=0.0078125, ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.08838834764831845,
        ssm_multipliers=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                         0.3535533905932738),
        mlp_multipliers=(0.1767766952966369, 0.011160714285714284))


@pytest.mark.parametrize("step", ["update", "scan"])
def test_ssm_kernels_compile_in_place(v5e, step):
    """The decode update over 128 lanes and the chunk scan of one 256-token
    chunk at Falcon-H1-34B's mixer sizes, on the whole 2.16 GB state pool:
    the pool is aliased to the output (no temporary at all of its size)."""
    from arkflow_tpu.ops import ssm_scan as ss

    f32 = jnp.float32
    pool = ((4, FALCON_SLOTS + 1, 32, 256, 128), f32)
    if step == "update":
        b = FALCON_SLOTS
        fn = lambda st, layer, rows, x, dt, a, bm, cm: ss.ssm_state_update(  # noqa: E731
            st, layer, rows, x, dt, a, bm, cm, kernel=True)
        shapes = (pool, ((), I32), ((b,), I32), ((b, 32, 128), f32),
                  ((b, 32), f32), ((32,), f32), ((b, 2, 256), f32),
                  ((b, 2, 256), f32))
    else:
        t = FALCON_CHUNK
        fn = lambda st, layer, rows, fr, x, dt, a, bm, cm: ss.ssm_chunk_scan(  # noqa: E731
            st, layer, rows, fr, x, dt, a, bm, cm, 128, kernel=True)
        shapes = (pool, ((), I32), ((1,), I32), ((1,), jnp.bool_),
                  ((1, t, 32, 128), f32), ((1, t, 32), f32), ((32,), f32),
                  ((1, t, 2, 256), f32), ((1, t, 2, 256), f32))
    one = SingleDeviceSharding(v5e[0])
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        *[jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("ssm_state_update" if step == "update" else "ssm_chunk_scan") in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024


def _hybrid_steps(devices):
    """The hybrid ``_decode`` (128 lanes) and ``_chunk`` (1 x 256) programs
    as the server jits them — both pool dicts donated — compiled for the
    described chip at the cell's shapes."""
    from arkflow_tpu.models import decoder as dec
    from arkflow_tpu.models.paged_decode import (init_page_pool, paged_decode_step,
                                                 paged_prefill_chunk)

    cfg = _falcon_cfg()
    one = SingleDeviceSharding(devices[0])

    def struct(a, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype, sharding=one)

    params = jax.tree_util.tree_map(
        struct, jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg)),
        dec.serve_dtypes(cfg))
    kp, vp = jax.tree_util.tree_map(struct, jax.eval_shape(
        lambda: init_page_pool(cfg, 1 + FALCON_SLOTS * FALCON_TABLE, PAGE,
                               slots=FALCON_SLOTS)))
    kern = dict(attention_kernel="paged")

    def decode(p, tok, lens, act, table, kp, vp):
        return paged_decode_step(p, cfg, tok, lens, act, table, kp, vp,
                                 return_logits=True, **kern)

    def chunk(p, ids, off, clen, table, rows, kp, vp):
        return paged_prefill_chunk(p, cfg, ids, off, clen, table, kp, vp,
                                   ssm_rows=rows, **kern)

    def compiled(fn, *operands):
        n = len(operands)
        return jax.jit(fn, donate_argnums=(n + 1, n + 2)).lower(
            params, *[jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in operands],
            kp, vp).compile()

    s = FALCON_SLOTS
    return (
        compiled(decode, ((s,), I32), ((s,), I32), ((s,), jnp.bool_),
                 ((s, FALCON_TABLE), I32)),
        compiled(chunk, ((1, FALCON_CHUNK), I32), ((1,), I32), ((1,), I32),
                 ((1, FALCON_TABLE), I32), ((1,), I32)))


def test_hybrid_steps_carry_the_state_pool_whole(v5e):
    """The state pool rides through the layer scan beside the K/V pools: no
    layer's 541 MB slab of states is copied out, updated and stacked back,
    and the whole programs fit the chip beside 8.8 GB of weights."""
    for step, name in zip(_hybrid_steps(v5e), ("ssm_state_update", "ssm_chunk_scan")):
        text = step.as_text()
        assert "tpu_custom_call" in text and name in text
        assert step.memory_analysis().temp_size_in_bytes < STATE_TEMP_LIMIT


# -- a layer pattern over per-head K/V with routed experts (K-EXAONE widths) ----

KEXAONE = dict(vocab_size=19200, dim=6144, layers=5, heads=64, kv_heads=8,
               head_dim=128, ffn=18432, max_seq=262144, rope_theta=1e6,
               norm_eps=1e-5, qk_norm=True, full_attention_rope=False,
               layer_types=("sliding_attention",) * 3 + ("full_attention",
                                                          "sliding_attention"),
               sliding_window=128, n_routed_experts=128, experts_held=(0, 16),
               num_experts_per_tok=8, n_shared_experts=1,
               moe_intermediate_size=2048, first_k_dense_replace=1,
               routed_scaling_factor=2.5)
#: 48 slots x 528 kept pages + scratch; 48 rings of 41 window pages + scratch
KEXAONE_SLOTS, KEXAONE_TABLE, KEXAONE_RING = 48, 528, 41


@pytest.mark.parametrize("rows,chunk", [(48, 1), (1, 512)], ids=["decode", "chunk512"])
def test_paged_window_attention_compiles(v5e, rows, chunk):
    """The per-head kernel with its lower bound over a ring of window pages,
    at the cell's lanes and chunk; named for the trace."""
    pool = ((4, 1 + KEXAONE_SLOTS * KEXAONE_RING, PAGE, 8, 128), BF16)
    compiled = _compile(
        lambda q, kp, vp, layer, ring, off: paged_flash_attention(
            q, kp, vp, layer, ring, off, window=128),
        v5e, ((rows, chunk, 64, 128), BF16), pool, pool, ((), I32),
        ((rows, KEXAONE_RING), I32), ((rows,), I32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "paged_window_attention" in text


def _kexaone_steps(devices):
    """The ``_decode`` (48 lanes) and ``_chunk`` (1 x 512) programs of the
    K-EXAONE cut as the server jits them, pools donated, for the described
    chip."""
    from arkflow_tpu.models import decoder as dec
    from arkflow_tpu.models.paged_decode import (init_page_pool, paged_decode_step,
                                                 paged_prefill_chunk)

    cfg = dec.DecoderConfig(**KEXAONE)
    repl = SingleDeviceSharding(devices[0])

    def struct(a, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype, sharding=repl)

    params = jax.tree_util.tree_map(
        struct, jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg)),
        dec.serve_dtypes(cfg))
    kp, vp = jax.tree_util.tree_map(struct, jax.eval_shape(
        lambda: init_page_pool(cfg, 1 + KEXAONE_SLOTS * KEXAONE_TABLE, PAGE,
                               1 + KEXAONE_SLOTS * KEXAONE_RING)))
    kern = dict(attention_kernel="paged")

    def decode(p, tok, lens, act, kept, ring, kp, vp):
        return paged_decode_step(p, cfg, tok, lens, act, (kept, ring), kp, vp,
                                 return_logits=True, **kern)

    def chunk(p, ids, off, clen, kept, ring, kp, vp):
        return paged_prefill_chunk(p, cfg, ids, off, clen, (kept, ring), kp, vp,
                                   **kern)

    def compiled(fn, *operands):
        n = len(operands)
        return jax.jit(fn, donate_argnums=(n + 1, n + 2)).lower(
            params, *[jax.ShapeDtypeStruct(s, d, sharding=repl)
                      for s, d in operands], kp, vp).compile()

    s = KEXAONE_SLOTS
    return (compiled(decode, ((s,), I32), ((s,), I32), ((s,), jnp.bool_),
                     ((s, KEXAONE_TABLE), I32), ((s, KEXAONE_RING), I32)),
            compiled(chunk, ((1, 512), I32), ((1,), I32), ((1,), I32),
                     ((1, KEXAONE_TABLE), I32), ((1, KEXAONE_RING), I32)))


def test_pattern_steps_carry_pools_and_stacks_whole(v5e):
    """Runs within the expert stack read their layers out of it inside the
    loop: no run's experts (1.2 GB a layer) and no pool (1.66 GB kept) is
    copied for a step. The programs' temporaries are about one layer's
    attention weights (229 MB: the compiler re-lays ``wq`` for its product
    inside the loop, in a whole-stack scan too, 102 MB there); both
    kernels' names are in the programs' text."""
    for step in _kexaone_steps(v5e):
        text = step.as_text()
        for name in ("paged_window_attention", "paged_flash_attention",
                     "moe_expert_swiglu"):
            assert name in text, name
        assert step.memory_analysis().temp_size_in_bytes < 300 * 1024 * 1024


# -- layers whose head sizes go by kind (MiMo-V2.5: mimo_l7) ------------------

MIMO = dict(vocab_size=19072, dim=4096, layers=7, heads=64, kv_heads=4,
            swa_kv_heads=8, head_dim=192, v_head_dim=128, swa_v_head_dim=128,
            ffn=16384, max_seq=1048576, rope_theta=1e7, swa_rope_theta=1e4,
            partial_rotary_factor=0.334, attention_value_scale=0.707,
            add_swa_attention_sink_bias=True, norm_eps=1e-5,
            layer_types=("full_attention",) + ("sliding_attention",) * 4
            + ("full_attention", "sliding_attention"),
            sliding_window=128, n_routed_experts=256, experts_held=(0, 16),
            num_experts_per_tok=8, n_shared_experts=0,
            moe_intermediate_size=2048, first_k_dense_replace=1)
#: 64 slots x 832 kept pages + scratch; 64 rings of 41 window pages + scratch
MIMO_SLOTS, MIMO_TABLE, MIMO_RING = 64, 832, 41


@pytest.mark.parametrize("rows,chunk", [(64, 1), (1, 512)], ids=["decode", "chunk512"])
@pytest.mark.parametrize("kind", ["full", "sliding"])
def test_hetero_paged_attention_compiles(v5e, kind, rows, chunk):
    """The kernel's own walk at the cell's shapes: keys of 192 held in two
    parts of 128 lanes (a pool layer a part), values of 128, 16 query heads
    a K/V head over 832 kept columns on a full layer, 8 over the 41-column
    ring with the sink on a sliding one. No pool is re-laid for the call
    (a [.., 4 heads, 256] key pool would be: 131 MB of temporaries at 2,001
    pages, its whole size)."""
    kvh, table, window = (4, MIMO_TABLE, 0) if kind == "full" else (8, MIMO_RING, 128)
    pages = 1 + MIMO_SLOTS * table
    compiled = _compile(
        lambda q, kp, vp, layer, t, off, sink: paged_flash_attention(
            q, kp, vp, layer, t, off, window=window,
            sink=sink if window else None),
        v5e, ((rows, chunk, 64, 192), BF16), ((2 * 2, pages, PAGE, kvh, 128), BF16),
        ((2, pages, PAGE, kvh, 128), BF16), ((), I32), ((rows, table), I32),
        ((rows,), I32), ((64,), jnp.float32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("paged_window_attention" in text) == bool(window)
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 1024 * 1024


def test_hetero_keys_of_192_lanes_are_not_walked_as_one_part(v5e):
    """Why a 192-wide key is held in parts: as one row of 192 (or of 256
    under 4 K/V heads) the kernel's own walk cannot copy its pages."""
    pool = ((2, 257, PAGE, 4, 192), BF16)
    with pytest.raises(Exception, match="row-major: cache_spec|aligned to tiling"):
        _compile(paged_flash_attention, v5e, ((8, 1, 64, 192), BF16), pool,
                 ((2, 257, PAGE, 4, 128), BF16), ((), I32), ((8, 32), I32),
                 ((8,), I32))


def test_hetero_steps_carry_pools_and_stacks_whole(v5e):
    """The ``_decode`` (64 lanes) and ``_chunk`` (1 x 512) programs of the
    MiMo-V2.5 cut as the server jits them, pools donated: all three kernels'
    names are in the text, and the temporaries (104 MB decode, 105 MB
    chunk) stay under 200 MB — no pool (5.2 GB kept) and no run's experts
    (0.8 GB a layer) is copied for a step; what is left is a layer's
    attention weights re-laid for their products inside the loop (the
    sliding stack is two runs read out of it by index: ROADMAP R4)."""
    from arkflow_tpu.models import decoder as dec
    from arkflow_tpu.models.paged_decode import (init_page_pool, paged_decode_step,
                                                 paged_prefill_chunk)

    cfg = dec.DecoderConfig(**MIMO)
    repl = SingleDeviceSharding(v5e[0])

    def struct(a, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype, sharding=repl)

    params = jax.tree_util.tree_map(
        struct, jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg)),
        dec.serve_dtypes(cfg))
    kp, vp = jax.tree_util.tree_map(struct, jax.eval_shape(
        lambda: init_page_pool(cfg, 1 + MIMO_SLOTS * MIMO_TABLE, PAGE,
                               1 + MIMO_SLOTS * MIMO_RING)))
    assert kp["kv"].shape == (2 * 2, 1 + 64 * 832, 16, 4, 128)
    assert vp["kv_window"].shape == (5, 1 + 64 * 41, 16, 8, 128)
    kern = dict(attention_kernel="paged")

    def decode(p, tok, lens, act, kept, ring, kp, vp):
        return paged_decode_step(p, cfg, tok, lens, act, (kept, ring), kp, vp,
                                 return_logits=True, **kern)

    def chunk(p, ids, off, clen, kept, ring, kp, vp):
        return paged_prefill_chunk(p, cfg, ids, off, clen, (kept, ring), kp, vp,
                                   **kern)

    def compiled(fn, *operands):
        n = len(operands)
        return jax.jit(fn, donate_argnums=(n + 1, n + 2)).lower(
            params, *[jax.ShapeDtypeStruct(s, d, sharding=repl)
                      for s, d in operands], kp, vp).compile()

    s = MIMO_SLOTS
    for step in (
            compiled(decode, ((s,), I32), ((s,), I32), ((s,), jnp.bool_),
                     ((s, MIMO_TABLE), I32), ((s, MIMO_RING), I32)),
            compiled(chunk, ((1, 512), I32), ((1,), I32), ((1,), I32),
                     ((1, MIMO_TABLE), I32), ((1, MIMO_RING), I32))):
        text = step.as_text()
        for name in ("paged_window_attention", "paged_flash_attention",
                     "moe_expert_swiglu"):
            assert name in text, name
        assert step.memory_analysis().temp_size_in_bytes < 200 * 1024 * 1024, \
            step.memory_analysis().temp_size_in_bytes


# -- LFM2-8B-A1B: conv layers among narrow-head attention layers, experts whole --

LFM2 = dict(vocab_size=65536, dim=2048, layers=12, heads=32, kv_heads=8, head_dim=64,
            ffn=7168, max_seq=128000, rope_theta=1e6, norm_eps=1e-5, qk_norm=True,
            layer_types=("conv", "conv", "full_attention", "conv", "conv", "conv",
                         "full_attention", "conv", "conv", "conv", "full_attention",
                         "conv"),
            conv_L_cache=3, n_routed_experts=32, num_experts_per_tok=4,
            moe_intermediate_size=1792, first_k_dense_replace=2, norm_topk_eps=1e-6)
#: 128 slots x 288 kept pages + scratch: every slot's whole table
LFM2_SLOTS, LFM2_TABLE = 128, 288


def test_conv_steps_carry_pools_and_stacks_whole(v5e):
    """The ``_decode`` (128 lanes) and ``_chunk`` (1 x 256) programs of the
    LFM2 cut as the server jits them, pools donated: the narrow-head walk
    and the expert kernel are in the text, and the temporaries stay under
    300 MB — no pool (3 x 1.21 GB of row-major K/V pages, which XLA re-laid
    around every step as [.., 8 heads, 64]; the conv windows, 9.5 MB, are
    updated by a scatter of the lanes' rows in place) and no run's experts
    (0.7 GB a layer) is copied for a step."""
    from arkflow_tpu.models import decoder as dec
    from arkflow_tpu.models.paged_decode import (init_page_pool, paged_decode_step,
                                                 paged_prefill_chunk)

    cfg = dec.DecoderConfig(**LFM2)
    repl = SingleDeviceSharding(v5e[0])

    def struct(a, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype, sharding=repl)

    params = jax.tree_util.tree_map(
        struct, jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg)),
        dec.serve_dtypes(cfg))
    kp, vp = jax.tree_util.tree_map(struct, jax.eval_shape(
        lambda: init_page_pool(cfg, 1 + LFM2_SLOTS * LFM2_TABLE, PAGE,
                               slots=LFM2_SLOTS)))
    assert kp["kv"].shape == vp["kv"].shape == (3, 1 + 128 * 288, 16, 512)
    assert kp["conv"].shape == (9, 129, 2, 2048)
    kern = dict(attention_kernel="paged")

    def decode(p, tok, lens, act, table, kp, vp):
        return paged_decode_step(p, cfg, tok, lens, act, table, kp, vp,
                                 return_logits=True, **kern)

    def chunk(p, ids, off, clen, table, rows, kp, vp):
        return paged_prefill_chunk(p, cfg, ids, off, clen, table, kp, vp,
                                   ssm_rows=rows, **kern)

    def compiled(fn, *operands):
        n = len(operands)
        return jax.jit(fn, donate_argnums=(n + 1, n + 2)).lower(
            params, *[jax.ShapeDtypeStruct(s, d, sharding=repl)
                      for s, d in operands], kp, vp).compile()

    s = LFM2_SLOTS
    for step in (
            compiled(decode, ((s,), I32), ((s,), I32), ((s,), jnp.bool_),
                     ((s, LFM2_TABLE), I32)),
            compiled(chunk, ((1, 256), I32), ((1,), I32), ((1,), I32),
                     ((1, LFM2_TABLE), I32), ((1,), I32))):
        text = step.as_text()
        for name in ("paged_flash_attention", "moe_expert_swiglu"):
            assert name in text, name
        assert step.memory_analysis().temp_size_in_bytes < 300 * 1024 * 1024, \
            step.memory_analysis().temp_size_in_bytes


def test_a_conv_routed_fused_step_compiles_at_lfm2_widths(v5e):
    """``lfm2_l12``'s ``_fused`` program — 128 lanes and a 256-token chunk,
    ONE block of 384 rows through twelve layers at the published widths, the
    pools donated — compiled for the described chip: the narrow-head walk
    twice an attention layer (the two steps' own calls), the expert product
    ONCE a layer and grouped (384 rows are three token tiles), the conv
    windows a part at a time, no pool and no run's experts copied, and the
    counters by row range beside the logits."""
    from arkflow_tpu.models import decoder as dec
    from arkflow_tpu.models.paged_decode import init_page_pool, paged_fused_step

    cfg = dec.DecoderConfig(**LFM2)
    chip = SingleDeviceSharding(v5e[0])

    def struct(a, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype, sharding=chip)

    params = jax.tree_util.tree_map(
        struct, jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg)),
        dec.serve_dtypes(cfg))
    kp, vp = jax.tree_util.tree_map(struct, jax.eval_shape(
        lambda: init_page_pool(cfg, 1 + LFM2_SLOTS * LFM2_TABLE, PAGE,
                               slots=LFM2_SLOTS)))
    s, table = LFM2_SLOTS, LFM2_TABLE
    operands = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip) for shape, dtype in (
        ((s,), I32), ((s,), I32), ((s,), jnp.bool_), ((s, table), I32),
        ((1, 256), I32), ((1,), I32), ((1,), I32), ((1, table), I32))]
    rows = jax.ShapeDtypeStruct((1,), I32, sharding=chip)

    def step(p, rows, *a):
        return paged_fused_step(p, cfg, *a, return_logits=True,
                                attention_kernel="paged", ssm_rows=rows)

    compiled = jax.jit(step, donate_argnums=(10, 11)).lower(
        params, rows, *operands, kp, vp).compile()
    text = compiled.as_text()
    assert "moe_expert_grouped" in text and "paged_flash_attention" in text
    logits, _, _, counters = jax.eval_shape(step, params, rows, *operands, kp, vp)
    assert logits.shape == (s + 1, 65536) and counters.shape == (3, 3)
    # as the two steps apart: no pool (3 x 1.21 GB) and no run's experts
    assert compiled.memory_analysis().temp_size_in_bytes < 300 * 1024 * 1024, \
        compiled.memory_analysis().temp_size_in_bytes


# -- the matrix-state kind (Qwen3-Next-80B-A3B widths, 128 slots, chunk 512) ----

QWEN3NEXT = dict(vocab_size=18992, dim=2048, layers=8, heads=16, kv_heads=2,
                 head_dim=256, ffn=5120, max_seq=262144, rope_theta=10000000,
                 norm_eps=1e-6, qk_norm=True, partial_rotary_factor=0.25,
                 attention_gate_type="elementwise", norm_unit_offset=True,
                 layer_types=(("linear_attention",) * 3 + ("full_attention",)) * 2,
                 linear_num_key_heads=16, linear_num_value_heads=32,
                 linear_key_head_dim=128, linear_value_head_dim=128,
                 linear_conv_kernel_dim=4, n_routed_experts=512,
                 experts_held=(0, 64), num_experts_per_tok=10, n_shared_experts=1,
                 shared_expert_gate=True, moe_intermediate_size=512,
                 first_k_dense_replace=0, scoring_func="softmax",
                 topk_method="greedy")
#: 128 slots x 576 kept pages + scratch: every slot's whole table
QWEN3NEXT_SLOTS, QWEN3NEXT_TABLE, QWEN3NEXT_CHUNK = 128, 576, 512


@pytest.mark.parametrize("step", ["update", "scan"])
def test_gdn_kernels_compile_in_place(v5e, step):
    """The decode update over 128 lanes and the chunk scan of one 512-token
    chunk at Qwen3-Next's mixer sizes, on the whole 1.62 GB state pool: the
    pool is aliased to the output, and k and q reach the update as ROWS (a
    column operand a head would be padded to 64 x its bytes in HBM)."""
    from arkflow_tpu.ops import gdn_scan as gs

    f32 = jnp.float32
    pool = ((6, QWEN3NEXT_SLOTS + 1, 32, 128, 128), f32)
    if step == "update":
        b = QWEN3NEXT_SLOTS
        fn = lambda st, layer, rows, q, k, v, g, beta: gs.gdn_state_update(  # noqa: E731
            st, layer, rows, q, k, v, g, beta, kernel=True)
        heads = ((b, 32, 128), f32)
        shapes = (pool, ((), I32), ((b,), I32), heads, heads, heads,
                  ((b, 32), f32), ((b, 32), f32))
    else:
        t = QWEN3NEXT_CHUNK
        fn = lambda st, layer, rows, fr, q, k, v, g, beta: gs.gdn_chunk_scan(  # noqa: E731
            st, layer, rows, fr, q, k, v, g, beta, kernel=True)
        heads = ((1, t, 32, 128), f32)
        shapes = (pool, ((), I32), ((1,), I32), ((1,), jnp.bool_), heads, heads,
                  heads, ((1, t, 32), f32), ((1, t, 32), f32))
    one = SingleDeviceSharding(v5e[0])
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        *[jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("gdn_state_update" if step == "update" else "gdn_chunk_scan") in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024


@pytest.mark.parametrize("step", ["update", "scan"])
def test_kda_kernels_compile_in_place(v5e, step):
    """The decode update over 128 lanes and the chunk scan of one 512-token
    chunk at Kimi Linear's mixer sizes (32 heads of 128 x 128, a decay a key
    channel), on the whole 1.62 GB state pool, aliased to the output: the
    decay reaches the update as ROWS beside k and q, and the scan's level
    products, its transposes and its one-hot gathers are the chip
    compiler's to accept."""
    from arkflow_tpu.ops import kda_scan as ks

    f32 = jnp.float32
    pool = ((6, 129, 32, 128, 128), f32)
    if step == "update":
        b = 128
        fn = lambda st, layer, rows, q, k, v, g, beta: ks.kda_state_update(  # noqa: E731
            st, layer, rows, q, k, v, g, beta, kernel=True)
        heads = ((b, 32, 128), f32)
        shapes = (pool, ((), I32), ((b,), I32), heads, heads, heads, heads,
                  ((b, 32), f32))
    else:
        t = 512
        fn = lambda st, layer, rows, fr, q, k, v, g, beta: ks.kda_chunk_scan(  # noqa: E731
            st, layer, rows, fr, q, k, v, g, beta, kernel=True)
        heads = ((1, t, 32, 128), f32)
        shapes = (pool, ((), I32), ((1,), I32), ((1,), jnp.bool_), heads, heads,
                  heads, heads, ((1, t, 32), f32))
    one = SingleDeviceSharding(v5e[0])
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        *[jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("kda_state_update" if step == "update" else "kda_chunk_scan") in text
    assert compiled.memory_analysis().temp_size_in_bytes < 96 * 1024 * 1024


@pytest.mark.parametrize("kvh,pool_kvh,relaid", [(2, 2, True), (2, 1, False)],
                         ids=["joined", "a-head-a-layer"])
def test_a_head_of_256_on_two_kv_heads_is_viewed_in_place_a_head_a_layer(
        v5e, kvh, pool_kvh, relaid):
    """What ``GqaSpec.split_heads`` is for: pools [.., 2 heads, 256] are
    re-laid WHOLE for every call of the paged kernel (two pool-sized
    temporaries), pools [.., 1, 256] — a head a layer — are not."""
    pages = 4097
    pool = ((2 * kvh // pool_kvh, pages, PAGE, pool_kvh, 256), BF16)
    h = 16 * pool_kvh // kvh
    compiled = _compile(
        lambda q, k, v, layer, table, off: paged_flash_attention(
            q, k, v, layer, table, off),
        v5e, ((128, 1, h, 256), BF16), pool, pool, ((), I32), ((128, 576), I32),
        ((128,), I32))
    one_pool = 2 * pages * PAGE * kvh * 256 * 2
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert (temp > one_pool) == relaid, (temp, one_pool)


def test_gdn_steps_carry_pools_and_stacks_whole(v5e):
    """The ``_decode`` (128 lanes) and ``_chunk`` (1 x 512) programs of the
    Qwen3-Next cut as the server jits them, pools donated: the page walk, the
    expert kernel and the delta rule's kernel are in the text, and the
    temporaries stay under 300 MB — no pool (1.62 GB of float32 states, 4 x
    1.21 GB of K/V pages a head a layer) and no run's experts (1.2 GB a run
    of six) is copied for a step."""
    from arkflow_tpu.models import decoder as dec
    from arkflow_tpu.models.paged_decode import (init_page_pool, paged_decode_step,
                                                 paged_prefill_chunk)

    cfg = dec.DecoderConfig(**QWEN3NEXT)
    repl = SingleDeviceSharding(v5e[0])

    def struct(a, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype, sharding=repl)

    params = jax.tree_util.tree_map(
        struct, jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg)),
        dec.serve_dtypes(cfg))
    kp, vp = jax.tree_util.tree_map(struct, jax.eval_shape(
        lambda: init_page_pool(cfg, 1 + QWEN3NEXT_SLOTS * QWEN3NEXT_TABLE, PAGE,
                               slots=QWEN3NEXT_SLOTS)))
    assert kp["kv"].shape == vp["kv"].shape == (4, 1 + 128 * 576, 16, 1, 256)
    assert kp["gdn"].shape == (6, 129, 32, 128, 128)
    assert vp["gdn"].shape == (6, 129, 3, 8192)
    kern = dict(attention_kernel="paged")

    def decode(p, tok, lens, act, table, kp, vp):
        return paged_decode_step(p, cfg, tok, lens, act, table, kp, vp,
                                 return_logits=True, **kern)

    def chunk(p, ids, off, clen, table, rows, kp, vp):
        return paged_prefill_chunk(p, cfg, ids, off, clen, table, kp, vp,
                                   ssm_rows=rows, **kern)

    def compiled(fn, *operands):
        n = len(operands)
        return jax.jit(fn, donate_argnums=(n + 1, n + 2)).lower(
            params, *[jax.ShapeDtypeStruct(s, d, sharding=repl)
                      for s, d in operands], kp, vp).compile()

    s = QWEN3NEXT_SLOTS
    for kernel, step in (
            ("gdn_state_update", compiled(
                decode, ((s,), I32), ((s,), I32), ((s,), jnp.bool_),
                ((s, QWEN3NEXT_TABLE), I32))),
            ("gdn_chunk_scan", compiled(
                chunk, ((1, QWEN3NEXT_CHUNK), I32), ((1,), I32), ((1,), I32),
                ((1, QWEN3NEXT_TABLE), I32), ((1,), I32)))):
        text = step.as_text()
        for name in ("paged_flash_attention", "moe_expert_swiglu", kernel):
            assert name in text, name
        assert step.memory_analysis().temp_size_in_bytes < 300 * 1024 * 1024, \
            step.memory_analysis().temp_size_in_bytes


KIMI = dict(vocab_size=20480, dim=2304, layers=8, heads=32, ffn=9216,
            max_seq=1048576, norm_eps=1e-5, kv_lora_rank=512,
            qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
            mla_use_nope=True,
            layer_types=(("linear_attention",) * 3 + ("full_attention",)) * 2,
            linear_attn_config={"num_heads": 32, "head_dim": 128,
                                "short_conv_kernel_size": 4},
            n_routed_experts=256, experts_held=(0, 32), num_experts_per_tok=8,
            n_shared_experts=1, moe_intermediate_size=1024,
            first_k_dense_replace=1, routed_scaling_factor=2.446)
#: 128 slots x 1,072 kept pages + scratch: every slot's whole table
KIMI_SLOTS, KIMI_TABLE, KIMI_CHUNK = 128, 1072, 512


def test_kda_steps_carry_pools_and_stacks_whole(v5e):
    """The ``_decode`` (128 lanes) and ``_chunk`` (1 x 512) programs of the
    Kimi Linear cut as the server jits them, pools donated: the latent walk,
    the expert kernel and the per-channel delta rule's kernel are in the
    text, and the temporaries stay under 400 MB — no pool (1.62 GB of float32
    states, 2.81 GB of latent pages a pool) and no run's experts (0.9 GB a
    run of two; the ``kda_layers`` stack is walked by index, its two runs
    never sliced) is copied for a step."""
    from arkflow_tpu.models import decoder as dec
    from arkflow_tpu.models.paged_decode import (init_page_pool, paged_decode_step,
                                                 paged_prefill_chunk)

    cfg = dec.DecoderConfig(**KIMI)
    repl = SingleDeviceSharding(v5e[0])

    def struct(a, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype, sharding=repl)

    params = jax.tree_util.tree_map(
        struct, jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg)),
        dec.serve_dtypes(cfg))
    kp, vp = jax.tree_util.tree_map(struct, jax.eval_shape(
        lambda: init_page_pool(cfg, 1 + KIMI_SLOTS * KIMI_TABLE, PAGE,
                               slots=KIMI_SLOTS)))
    assert kp["latent"].shape == (2, 1 + 128 * 1072, 16, 512)
    assert vp["latent"].shape == (2, 1 + 128 * 1072, 16, 128)
    assert kp["kda"].shape == (6, 129, 32, 128, 128)
    assert vp["kda"].shape == (6, 129, 3, 12288)
    kern = dict(attention_kernel="paged")

    def decode(p, tok, lens, act, table, kp, vp):
        return paged_decode_step(p, cfg, tok, lens, act, table, kp, vp,
                                 return_logits=True, **kern)

    def chunk(p, ids, off, clen, table, rows, kp, vp):
        return paged_prefill_chunk(p, cfg, ids, off, clen, table, kp, vp,
                                   ssm_rows=rows, **kern)

    def compiled(fn, *operands):
        n = len(operands)
        return jax.jit(fn, donate_argnums=(n + 1, n + 2)).lower(
            params, *[jax.ShapeDtypeStruct(s, d, sharding=repl)
                      for s, d in operands], kp, vp).compile()

    s = KIMI_SLOTS
    for kernels, step in (
            (("kda_state_update", "moe_expert_swiglu"), compiled(
                decode, ((s,), I32), ((s,), I32), ((s,), jnp.bool_),
                ((s, KIMI_TABLE), I32))),
            (("kda_chunk_scan", "moe_expert_grouped"), compiled(
                chunk, ((1, KIMI_CHUNK), I32), ((1,), I32), ((1,), I32),
                ((1, KIMI_TABLE), I32), ((1,), I32)))):
        text = step.as_text()
        for name in ("mla_paged_attention", *kernels):
            assert name in text, name
        assert step.memory_analysis().temp_size_in_bytes < 400 * 1024 * 1024, \
            step.memory_analysis().temp_size_in_bytes


# -- the residual streams' mixing (ops/mhc_mix.py), at Xing4.0's widths --------

XING_STREAMS, XING_HIDDEN = 4, 3584


@pytest.mark.parametrize("rows", [512, 32, 16])
def test_mhc_mixing_kernels_compile_at_xing4_widths(v5e, rows):
    """A chunk's 512 rows (four token tiles), a decode step's 32 lanes and
    the fewest rows the kernels take, each ONE grid step of its own rows:
    the 128 x 128 transposes of a short tile, the [14336, 128] operand in
    one buffer, 64 MB of fast memory asked for."""
    from arkflow_tpu.ops import mhc_mix as mm

    n, c = XING_STREAMS, XING_HIDDEN
    k = mm.n_coefficients(n)
    kw = dict(n=n, iters=20, eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6)
    pre = _compile(
        lambda x, phi, b, alpha: mm.mhc_pre_kernel(
            x, {"phi": phi, "b": b, "alpha": alpha}, **kw),
        v5e, ((1, rows, n * c), BF16), ((n * c, k), jnp.float32),
        ((k,), jnp.float32), ((3,), jnp.float32))
    post = _compile(mm.mhc_post_kernel, v5e, ((1, rows, n * c), BF16),
                    ((1, rows, c), BF16), ((1, rows, 128), jnp.float32))
    assert "mhc_pre" in pre.as_text() and "mhc_post" in post.as_text()


# -- a compacting window cache (EvaByte), at the cell's shapes -------------------

EVA_WINDOW, EVA_CHUNK, EVA_KVH, EVA_SLOTS, EVA_TABLE = 2048, 16, 32, 20, 248


@pytest.mark.parametrize("rows", [1, EVA_SLOTS], ids=["a_close", "a_probe_of_20"])
def test_eva_summarise_compiles_at_evabyte_widths(v5e, rows):
    """One window's 2,048 rows of 32 K/V heads x 128 pooled to 128 summary
    rows: 16 programs of 8 summary rows, 1 MB of K and of V each."""
    from arkflow_tpu.ops.eva_summarise import eva_summarise

    kv = ((rows, EVA_WINDOW, EVA_KVH, 128), BF16)
    leaf = ((EVA_KVH, 128), jnp.float32)
    compiled = _compile(
        lambda k, v, phi, mu: eva_summarise(k, v, phi, mu, chunk=EVA_CHUNK),
        v5e, kv, kv, leaf, leaf)
    assert "eva_summarise" in compiled.as_text()


def test_eva_steps_close_windows_without_copying_the_pools(v5e):
    """The cell's decode step (20 lanes) and chunk (512 tokens) at depth 2:
    the close is a loop over the closing rows that carries both pools in
    place — no pool-sized temporary (a ``cond`` around it copied both: 9.7 GB
    at depth 8) — and both programs call the attention kernel and the
    summariser."""
    from arkflow_tpu.models import decoder as dec
    from arkflow_tpu.models import paged_decode as pd

    cfg = dec.DecoderConfig(
        vocab_size=320, dim=4096, layers=2, heads=32, kv_heads=32, ffn=11008,
        max_seq=32768, rope_theta=1e5, norm_eps=1e-5, norm_unit_offset=True,
        attention_class="eva", window_size=EVA_WINDOW, chunk_size=EVA_CHUNK,
        num_pred_heads=8, fp32_skip_add=True, fp32_logits=True)
    assert pd.eva_table_pages(cfg, 16, 30720 + 1024) == EVA_TABLE
    one = SingleDeviceSharding(v5e[0])
    shapes = jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg))
    params = jax.tree_util.tree_map(
        lambda s, d: jax.ShapeDtypeStruct(s.shape, d, sharding=one),
        shapes, dec.serve_dtypes(cfg))
    kp, vp = (jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one)
              for a in jax.eval_shape(lambda: pd.init_page_pool(
                  cfg, 1 + EVA_SLOTS * EVA_TABLE, 16)))
    arg = lambda shape, d=I32: jax.ShapeDtypeStruct(shape, d, sharding=one)  # noqa: E731
    kern = dict(attention_kernel="paged", kernel_interpret=False)
    s = EVA_SLOTS
    steps = {
        "decode": (lambda p, tok, n, act, t, k, v: pd.paged_decode_step(
            p, cfg, tok, n, act, t, k, v, return_logits=True, **kern),
            (arg((s,)), arg((s,)), arg((s,), jnp.bool_), arg((s, EVA_TABLE)))),
        "chunk": (lambda p, ids, off, n, t, k, v: pd.paged_prefill_chunk(
            p, cfg, ids, off, n, t, k, v, **kern),
            (arg((1, 512)), arg((1,)), arg((1,)), arg((1, EVA_TABLE))))}
    pool_bytes = 2 * 4961 * 16 * 32 * 128 * 2
    for name, (fn, operands) in steps.items():
        step = jax.jit(fn, donate_argnums=(5, 6)).lower(
            params, *operands, kp, vp).compile()
        text = step.as_text()
        assert "paged_flash_attention" in text and "eva_summarise" in text, name
        mem = step.memory_analysis()
        assert mem.alias_size_in_bytes >= 2 * pool_bytes, name
        assert mem.temp_size_in_bytes < 200 * 1024 * 1024, (
            name, mem.temp_size_in_bytes)


# -- one-mixer blocks: Mamba-2 | relu-squared experts | position-free GQA ------
# (NVIDIA-Nemotron-3-Nano-30B-A3B's widths, the cell's cut and shapes)

NEMOTRON = dict(vocab_size=65536, dim=2688, layers=13, heads=32, kv_heads=2,
                head_dim=128, max_seq=262144, norm_eps=1e-5,
                hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*"
                                        "EMEMEMEM*EMEMEMEME",
                full_attention_rope=False, mamba_n_heads=64, mamba_d_head=64,
                mamba_d_state=128, mamba_n_groups=8, mamba_d_conv=4,
                mamba_chunk_size=128, mlp_hidden_act="relu2",
                n_routed_experts=128, experts_held=(0, 64),
                num_experts_per_tok=6, n_shared_experts=1,
                moe_intermediate_size=1856,
                moe_shared_expert_intermediate_size=3712,
                routed_scaling_factor=2.5)
#: 192 slots x 280 kept pages + scratch: every slot's whole table
NEMOTRON_SLOTS, NEMOTRON_TABLE, NEMOTRON_CHUNK = 192, 280, 512


@pytest.mark.parametrize("tokens", [16, 192, 512], ids=["tile", "decode192", "chunk512"])
def test_moe_expert_relu2_compiles(v5e, tokens):
    """The two-matrix expert product at Nemotron-3-Nano's widths over the
    cell's five expert layers (64 held + the shared expert as two): the
    one-tile kernel up to 128 rows, the grouped one for the cell's 192 lanes
    and its 512-row chunk; no layer's 1.3 GB is copied out for either. (At
    the published 1,856 columns, no multiple of 128 lanes, the compiler
    re-lays the whole stack, 3.3 GB, for every call: the stack holds 1,920,
    ``DecoderConfig.expert_width_held``.)"""
    from arkflow_tpu.ops.moe_experts import moe_expert_relu2

    layers, e, d, f = 5, 66, 2688, 1920      # 1,856 as held: whole 128-lane rows
    compiled = _compile(
        lambda x, cw, wu, wd, layer: moe_expert_relu2(x, cw, wu, wd, layer),
        v5e, ((tokens, d), BF16), ((tokens, e), jnp.float32),
        ((layers, e, d, f), BF16), ((layers, e, f, d), BF16), ((), I32))
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("moe_expert_relu2_grouped" in text) == (tokens > 128)
    assert "moe_expert_relu2" in text and "swiglu" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 1024 * 1024


@pytest.mark.parametrize("step", ["update", "scan"])
def test_ssm_kernels_compile_on_packed_narrow_heads(v5e, step):
    """The decode update over 192 lanes and the scan of one 512-token chunk
    at Nemotron-3-Nano's mixer sizes — 64 heads of 64, state 128, 8 groups —
    on the 2.4 GB state pool AS HELD, two heads side by side on the lanes
    ([.., 32, 128, 128]): the update runs on the pool in place; the scan
    takes its one row out a head each and writes it back (a few MB)."""
    from arkflow_tpu.ops import ssm_scan as ss

    f32 = jnp.float32
    assert ss.heads_packed(64, 8, 64) == 2
    pool = ((6, NEMOTRON_SLOTS + 1, 32, 128, 128), f32)
    if step == "update":
        b = NEMOTRON_SLOTS
        fn = lambda st, layer, rows, x, dt, a, bm, cm: ss.ssm_state_update(  # noqa: E731
            st, layer, rows, x, dt, a, bm, cm, kernel=True)
        shapes = (pool, ((), I32), ((b,), I32), ((b, 64, 64), f32),
                  ((b, 64), f32), ((64,), f32), ((b, 8, 128), f32),
                  ((b, 8, 128), f32))
    else:
        t = NEMOTRON_CHUNK
        fn = lambda st, layer, rows, fr, x, dt, a, bm, cm: ss.ssm_chunk_scan(  # noqa: E731
            st, layer, rows, fr, x, dt, a, bm, cm, 128, kernel=True)
        shapes = (pool, ((), I32), ((1,), I32), ((1,), jnp.bool_),
                  ((1, t, 64, 64), f32), ((1, t, 64), f32), ((64,), f32),
                  ((1, t, 8, 128), f32), ((1, t, 8, 128), f32))
    one = SingleDeviceSharding(v5e[0])
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        *[jax.ShapeDtypeStruct(s, d, sharding=one) for s, d in shapes]).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("ssm_state_update" if step == "update" else "ssm_chunk_scan") in text
    # the update's B and C ride as COLUMNS, [lanes, 8 groups, 128, 2], tiled to
    # 128 lanes: 100 MB of the 201 (Falcon-H1's [128, 2, 256, 2]: 33 MB)
    assert compiled.memory_analysis().temp_size_in_bytes < 256 * 1024 * 1024


def test_one_mixer_steps_carry_pools_and_stacks_whole(v5e):
    """The ``_decode`` (192 lanes) and ``_chunk`` (1 x 512) programs of the
    Nemotron-3-Nano cut as the server jits them, pools donated: thirteen runs
    of one block each over three stacks walked by index; the per-head kernel
    on 16 query heads a K/V head, the recurrence's two kernels and the
    two-matrix expert product are in the text; no pool (2.4 GB of float32
    states) and no stack's experts (6.6 GB) is copied for a step."""
    from arkflow_tpu.models import decoder as dec
    from arkflow_tpu.models.paged_decode import (init_page_pool, paged_decode_step,
                                                 paged_prefill_chunk)

    cfg = dec.DecoderConfig(**NEMOTRON)
    repl = SingleDeviceSharding(v5e[0])

    def struct(a, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype, sharding=repl)

    params = jax.tree_util.tree_map(
        struct, jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg)),
        dec.serve_dtypes(cfg))
    kp, vp = jax.tree_util.tree_map(struct, jax.eval_shape(
        lambda: init_page_pool(cfg, 1 + NEMOTRON_SLOTS * NEMOTRON_TABLE, PAGE,
                               slots=NEMOTRON_SLOTS)))
    assert kp["kv"].shape == (2, 1 + 192 * 280, 16, 2, 128)
    assert kp["ssm"].shape == (6, 193, 32, 128, 128)
    assert vp["ssm"].shape == (6, 193, 3, 6144)
    assert params["moe_layers"]["experts"]["w_up"].shape == (5, 66, 2688, 1920)
    kern = dict(attention_kernel="paged")

    def decode(p, tok, lens, act, table, kp, vp):
        return paged_decode_step(p, cfg, tok, lens, act, table, kp, vp,
                                 return_logits=True, **kern)

    def chunk(p, ids, off, clen, table, rows, kp, vp):
        return paged_prefill_chunk(p, cfg, ids, off, clen, table, kp, vp,
                                   ssm_rows=rows, **kern)

    def compiled(fn, *operands):
        n = len(operands)
        return jax.jit(fn, donate_argnums=(n + 1, n + 2)).lower(
            params, *[jax.ShapeDtypeStruct(s, d, sharding=repl)
                      for s, d in operands], kp, vp).compile()

    s = NEMOTRON_SLOTS
    for kernels, step in (
            (("ssm_state_update",), compiled(
                decode, ((s,), I32), ((s,), I32), ((s,), jnp.bool_),
                ((s, NEMOTRON_TABLE), I32))),
            (("ssm_chunk_scan",), compiled(
                chunk, ((1, NEMOTRON_CHUNK), I32), ((1,), I32), ((1,), I32),
                ((1, NEMOTRON_TABLE), I32), ((1,), I32)))):
        text = step.as_text()
        for name in ("paged_flash_attention", "moe_expert_relu2_grouped", *kernels):
            assert name in text, name
        assert step.memory_analysis().temp_size_in_bytes < 400 * 1024 * 1024, \
            step.memory_analysis().temp_size_in_bytes
