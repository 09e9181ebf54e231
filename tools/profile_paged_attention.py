"""Time the paged attention kernels alone, at the shapes the benchmark's cells serve.

The kernel-alone microbench behind PERF.md's S11 / S13 numbers (PR 41, 42,
43 and 44 each needed it): one call a layer of the per-head paged kernel
(``paged_flash_attention``) or of the latent one (``mla_paged_attention``:
``kanana2_l6``'s plain form, ``dots3_l5``'s under the indexer's choice and
under a window) — a decode step over many lanes, or one row's prefill chunk
at an offset — at a cell's geometry, table width and lane count, on the chip
this process holds. One JSON line a point:

- ``us_per_call``: best of ``--rounds`` timed loops of ``--reps`` calls
  (``lax.fori_loop`` over the pool's layers inside ONE jitted program, so
  the host's dispatch is paid once a loop, not a call);
- ``held_mb`` / ``held_roof_pct``: the bytes of the K and V rows the call's
  queries can attend AS HELD in the pools (a key held in parts counts its
  padding), and that over the chip's HBM peak (``benchmark/peaks.json``) and
  the call's time: a decode call's roof. A chunk re-reads its context a
  query tile, so its share of that roof says how far it is from one read;
- ``per_kv_head``, ``tile_c``, ``tiles``, ``group``: how the call was cut
  (``ops/ragged_attention.per_kv_head``, ``_page_group``; a latent point:
  ``group`` alone, ``_latent_group``).

    python tools/profile_paged_attention.py                      # every point
    python tools/profile_paged_attention.py --cell mimo_l7_full --kind chunk
    python tools/profile_paged_attention.py --group-max 16,32,64 # a sweep
    python tools/profile_paged_attention.py --cell lfm2_l12 --form gather
    python tools/profile_paged_attention.py --cell xing4_l10 --table runs --run 2,4,8
    python tools/profile_paged_attention.py --cell mimo_l7_full --kind decode --table runs --run 0,8 --check

A head narrower than 128 lanes (``lfm2_l12``: 8 K/V heads of 64) has
row-major pools and the narrow-head walk where the checkout has one
(``ops/ragged_attention.narrow_run``); an older checkout (``--root``) gets
pools [.., kv heads, 64] and walks them by its grid. ``--form gather`` times
the plain-XLA form a ``decode_kernel: gather`` server runs instead (the
layer's context gathered through the table, K and V repeated to the query
heads, masked attention).

``--group-max`` sets the module's group ceilings for the run (a
microbench's lever, not a serving knob; for a latent point it IS the group,
under a window too, where the kernel would take a tile's whole walk).
``--run`` sets the pages ONE copy of a walk moves where the table lays them
side by side (the module's ``PAGE_RUN``; 0: the walk without runs, a page a
copy, on the same checkout), the per-head and the latent walk alike.
``--check`` compares each point's output with the gather reference on the
device and, where the walk takes runs, BIT FOR BIT with the same call
without (``same_as_run_0``). Needs a TPU: on the CPU the
kernel is interpreted and a time says nothing (``--interpret`` runs tiny
shapes there to rehearse the control flow; its lines say ``"rehearsal"``).
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

PAGE = 16

#: a cell's per-head attention as one chip sees it: query heads, K/V heads,
#: key width (192: held in two parts of 128 lanes), value width, window,
#: sink, lanes of a decode step, table columns (a ring's under a window),
#: chunk length, the chunk offsets timed, a decode lane's contexts
#: (lognormal median / sigma / clip, as the cell's traffic draws them)
CELLS = {
    "mistral_l6": dict(h=32, kvh=8, dk=128, dv=128, lanes=16, cols=136,
                       chunk=128, offsets=(512, 1920), ctx=(640, 0.6, 190, 2176)),
    "mistral_tp4_local": dict(h=8, kvh=2, dk=128, dv=128, lanes=16, cols=136,
                              chunk=128, offsets=(512, 1920),
                              ctx=(640, 0.6, 190, 2176)),
    "falconh1_l4": dict(h=20, kvh=4, dk=128, dv=128, lanes=128, cols=64,
                        chunk=256, offsets=(0, 256), ctx=(384, 0.8, 30, 1024)),
    "kexaone_l5_full": dict(h=64, kvh=8, dk=128, dv=128, lanes=48, cols=528,
                            chunk=512, offsets=(512, 2048, 7680),
                            ctx=(900, 1.2, 64, 8448)),
    "kexaone_l5_window": dict(h=64, kvh=8, dk=128, dv=128, window=128, lanes=48,
                              cols=41, chunk=512, offsets=(2048,),
                              ctx=(900, 1.2, 64, 8448)),
    "mimo_l7_full": dict(h=64, kvh=4, dk=192, dv=128, lanes=64, cols=832,
                         chunk=512, offsets=(1024, 4096, 11776),
                         ctx=(4600, 0.6, 1100, 13300)),
    "mimo_l7_window": dict(h=64, kvh=8, dk=192, dv=128, window=128, sink=True,
                           lanes=64, cols=41, chunk=512, offsets=(4096,),
                           ctx=(4600, 0.6, 1100, 13300)),
    # 8 K/V heads of 64: row-major pools, the narrow-head walk; every lane of
    # a decode point at ONE context (``decode_ctx``: a point each). The table
    # as ISSUE 46 sized it (768 new tokens: 304 columns) and PERF.md's
    # kernel-alone table was measured; the cell serves 288 since (512 new)
    "lfm2_l12": dict(h=32, kvh=8, dk=64, dv=64, lanes=128, cols=304, chunk=256,
                     offsets=(512, 2048, 4608), decode_ctx=(512, 2048, 4863)),
    # 2 K/V heads of 256 (keys AND values): the pools hold a head a layer,
    # [heads x layers, pages, 16, 1, 256], and the kernel is called a head at
    # a time (``split``: ``GqaSpec.split_heads``); ``_joined`` is what the
    # pools would be without that, [layers, pages, 16, 2, 256], which XLA
    # re-lays whole for every call (PERF.md section 6, PR 49)
    "qwen3next_l8": dict(h=16, kvh=2, dk=256, dv=256, split=True, lanes=128,
                         cols=576, chunk=512, offsets=(2048, 7680),
                         decode_ctx=(3072,)),
    "qwen3next_l8_joined": dict(h=16, kvh=2, dk=256, dv=256, lanes=128,
                                cols=576, chunk=512, offsets=(2048,),
                                decode_ctx=(3072,)),
    # 32 K/V heads, a query head each, and a cache whose rows are not
    # positions: a lane's table holds its closed windows' summary rows (128 a
    # window), then its open window's (``attention_class: eva``). A chunk is
    # the last 512 tokens of a 2,048-row window behind 1 / 8 / 15 closed
    # windows; a decode lane sits in the middle of its window behind as many
    "evabyte_l8": dict(h=32, kvh=32, dk=128, dv=128, lanes=20, cols=248,
                       chunk=512, offsets=(1664, 2560, 3456),
                       decode_ctx=(1152, 2048, 2944)),
    # the latent kernel (``lat``: one shared row a token of that width beside
    # a rope key of ``rope``; ``topk``: under an indexer's choice of so many)
    "kanana2_l6": dict(h=32, lat=512, rope=64, lanes=16, cols=136, chunk=128,
                       offsets=(512, 1920), ctx=(640, 0.6, 190, 2176)),
    "dots3_l5_full": dict(h=128, lat=512, rope=64, topk=2048, lanes=32, cols=784,
                          chunk=512, offsets=(2048, 4096, 11776),
                          ctx=(4800, 0.5, 2400, 12500)),
    "dots3_l5_window": dict(h=64, lat=1024, rope=64, window=513, lanes=32,
                            cols=65, chunk=512, offsets=(4096,),
                            ctx=(4800, 0.5, 2400, 12500)),
    # the longest walk of the matrix: 2k-15k tokens in, 512 out
    "xing4_l10": dict(h=32, lat=512, rope=64, lanes=32, cols=992, chunk=512,
                      offsets=(2048, 6144, 14848), ctx=(6400, 0.5, 2048, 15872)),
}

#: pages a block of a ``--table runs`` table: the largest ``--run`` it can show
BLOCK = 8


def _points(args):
    for name, cell in CELLS.items():
        if args.cell != "all" and name not in args.cell.split(","):
            continue
        if args.kind in ("all", "decode"):
            for ctx in ([int(c) for c in args.decode_ctx.split(",") if c]
                        or cell.get("decode_ctx", (None,))):
                yield name, cell, "decode", ctx
        if args.kind in ("all", "chunk"):
            for off in cell["offsets"]:
                yield name, cell, "chunk", off


def _pools(cell, seed: int, tiny: bool, rope_held: bool = True,
           row_major: bool = True):
    """A cell's K and V pools (two layers, every lane's columns once, a key
    of 192 in two parts of 128 lanes; a head narrower than 128 lanes
    row-major where the checkout walks such pools, ``row_major``) and its
    sink logits; a latent cell's latent rows and rope keys, the keys in
    whole 128-lane rows with zeros behind them where the checkout holds them
    so (``rope_held``)."""
    import jax
    import jax.numpy as jnp

    lanes, cols = (min(cell["lanes"], 3), min(cell["cols"], 24)) if tiny else (
        cell["lanes"], cell["cols"])
    if "lat" in cell:
        keys = jax.random.split(jax.random.PRNGKey(seed), 2)
        pool = (2, 1 + lanes * cols, PAGE)
        r = jax.random.normal(keys[1], pool + (cell["rope"],), jnp.bfloat16) * 0.5
        if rope_held:
            r = jnp.pad(r, ((0, 0),) * 3 + ((0, -cell["rope"] % 128),))
        return (jax.random.normal(keys[0], pool + (cell["lat"],), jnp.bfloat16) * 0.5,
                r, None, lanes, cols)
    dk, dv, kvh = cell["dk"], cell["dv"], cell["kvh"]
    parts, held = (1, dk) if dk % 128 == 0 or dk < 128 else (-(-dk // 128), 128)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    fill = lambda key, shape: (jax.random.normal(key, shape, jnp.bfloat16) * 0.5)  # noqa: E731
    flat = row_major and dk == dv < 128
    if cell.get("split"):  # a head a layer: head j of layer l at 2 j + l
        k = fill(keys[0], (2 * kvh, 1 + lanes * cols, PAGE, 1, held))
        v = fill(keys[1], (2 * kvh, 1 + lanes * cols, PAGE, 1, dv))
        return k, v, None, lanes, cols
    k = fill(keys[0], (2 * parts, 1 + lanes * cols, PAGE,
                       *((kvh * held,) if flat else (kvh, held))))
    v = fill(keys[1], (2, 1 + lanes * cols, PAGE,
                       *((kvh * dv,) if flat else (kvh, dv))))
    sink = (jax.random.normal(keys[2], (cell["h"],), jnp.float32) + 2.0
            if cell.get("sink") else None)
    return k, v, sink, lanes, cols


def _queries(cell, kind, off, rng, lanes: int, cols: int, tiny: bool,
             runs: bool = False):
    """q, the table and the offsets of one point over a cell's pools: a
    row's pages any of the pool's (``permuted``: what a LIFO free list
    leaves) or, ``runs``, whole blocks of ``BLOCK`` neighbours, the blocks
    any of the pool's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    b, c = (lanes, 1) if kind == "decode" else (1, 32 if tiny else cell["chunk"])
    if kind == "decode" and off is not None:
        ctx = np.full((b,), off, np.float64)
    elif kind == "decode":
        med, sigma, lo, hi = cell["ctx"]
        ctx = np.clip(np.exp(rng.normal(np.log(med), sigma, b)), lo, hi)
    else:
        ctx = np.full((b,), off, np.float64)
    if not cell.get("window", 0):
        ctx = np.minimum(ctx, cols * PAGE - c)
    key = jax.random.PRNGKey(int(rng.randint(1 << 30)))
    pages = rng.permutation(lanes * cols)
    if runs:
        pages = (pages[pages < lanes * cols // BLOCK, None] * BLOCK
                 + np.arange(BLOCK)).reshape(-1)
    table = jnp.asarray(1 + pages[:b * cols].reshape(b, cols), jnp.int32)
    off = jnp.asarray(ctx.astype(np.int32))
    if "lat" not in cell:
        return (jax.random.normal(key, (b, c, cell["h"], cell["dk"]), jnp.bfloat16),
                table, off)
    kl, kr, ka = jax.random.split(key, 3)
    q = tuple(jax.random.normal(k, (b, c, cell["h"], w), jnp.bfloat16) * 0.2
              for k, w in ((kl, cell["lat"]), (kr, cell["rope"])))
    if not cell.get("topk"):
        return q, table, off
    # an indexer's choice: ``topk`` of a query's seen keys on average, drawn
    # key by key (every 16-token page holds one past a few thousand keys)
    pos = off[:, None] + jnp.arange(c)[None, :]                      # [b, c]
    seen = jnp.arange(cols * PAGE)[None, None, :] <= pos[..., None]
    u = jax.random.uniform(ka, seen.shape)
    allowed = seen & (u * (pos[..., None] + 1) < cell["topk"])
    return (*q, allowed.astype(jnp.float32)), table, off


def _held_bytes(cell, ctx, c) -> float:
    """Bytes of the K and V rows the call can attend, as the pools hold them."""
    import numpy as np

    window = cell.get("window", 0)
    last = ctx.astype(np.int64) + c
    keys = np.minimum(last, window + c - 1) if window else last
    if "lat" in cell:  # needed bytes: the rope key at its own width
        if cell.get("topk"):
            keys = np.minimum(keys, cell["topk"] + c - 1)
        return float(keys.sum()) * (cell["lat"] + cell["rope"]) * 2
    dk = cell["dk"] if cell["dk"] % 128 == 0 or cell["dk"] < 128 else (
        -(-cell["dk"] // 128) * 128)
    return float(keys.sum()) * cell["kvh"] * (dk + cell["dv"]) * 2


def _reference(q, k, v, table, off, cell):
    """The gather reference, float32, on the device (layer 1), a row at a
    time and the query heads of a K/V head side by side (no repeat)."""
    import jax
    import jax.numpy as jnp

    b, c, h, dk = q.shape
    kvh, parts = cell["kvh"], k.shape[0] // 2

    def row(args):
        q, table, off = args
        kk = jnp.concatenate([k[p * 2 + 1][table].reshape(-1, kvh, k.shape[-1] // (
            kvh if k.ndim == 4 else 1)) for p in range(parts)], axis=-1)
        kk = kk[..., :dk].astype(jnp.float32)
        vv = v[1][table].reshape(-1, kvh, cell["dv"]).astype(jnp.float32)
        qq = q.astype(jnp.float32).reshape(c, kvh, h // kvh, dk)
        s = jnp.einsum("cjgd,sjd->jgcs", qq, kk) * dk ** -0.5
        keep = jnp.arange(kk.shape[0])[None, :] <= off + jnp.arange(c)[:, None]
        s = jnp.where(keep[None, None], s, -1e30)
        p = jnp.exp(s - s.max(-1, keepdims=True))
        return jnp.einsum("jgcs,sjd->cjgd", p / p.sum(-1, keepdims=True), vv
                          ).reshape(c, h, cell["dv"])

    return jax.lax.map(row, (q, table, off))


def _gathered(q, k, v, layer, table, off, cell):
    """The plain-XLA form ``paged_decode._dense_layers`` runs under
    ``decode_kernel: gather``: the layer's context gathered through the
    table, K and V repeated to the query heads, masked attention in the
    pools' type (one part a key, no window, no sink)."""
    import jax.numpy as jnp

    from arkflow_tpu.models import common as cm

    b, c, h, _ = q.shape
    kvh = cell["kvh"]
    kk, vv = (jnp.repeat(pool[layer][table].reshape(b, -1, kvh, q.shape[-1]),
                         h // kvh, axis=2) for pool in (k, v))
    pos = off[:, None] + jnp.arange(c)[None, :]
    mask = jnp.arange(kk.shape[1])[None, None, None, :] <= pos[:, None, :, None]
    return cm.attention(q, kk, vv, mask)


def _latent_reference(q, c_pool, r_pool, table, off, cell):
    """The latent kernel's plain-XLA twin (layer 1), a row at a time."""
    import jax
    import jax.numpy as jnp

    from arkflow_tpu.models.paged_decode import _masked_latent_attention

    q_lat, q_rope, *allowed = q
    c = q_lat.shape[1]

    def row(args):
        q_lat, q_rope, table, off, *allowed = args
        cc = c_pool[1][table].reshape(1, -1, cell["lat"])
        rr = r_pool[1][table][..., :cell["rope"]].reshape(1, -1, cell["rope"])
        mask = jnp.arange(cc.shape[1])[None, :] <= off + jnp.arange(c)[:, None]
        if allowed:
            mask = mask & (allowed[0] > 0)
        return _masked_latent_attention(
            q_lat[None], q_rope[None], cc, rr, mask[None, None],
            (128 + cell["rope"]) ** -0.5)[0]

    return jax.lax.map(row, (q_lat, q_rope, table, off, *allowed))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="all",
                    help="a cell of CELLS, several with commas, or all")
    ap.add_argument("--kind", default="all", choices=["all", "decode", "chunk"])
    ap.add_argument("--group-max", default="",
                    help="comma-separated ceilings of pages a group to sweep")
    ap.add_argument("--table", default="permuted", choices=["permuted", "runs"],
                    help="runs: a row's pages in blocks of 8 neighbours (what "
                         "the server's allocator hands out), which a walk "
                         "copies --run pages a descriptor")
    ap.add_argument("--run", default="",
                    help="comma-separated pages a copy of a walk to sweep "
                         "(the module's PAGE_RUN; 0: no runs)")
    ap.add_argument("--decode-ctx", default="",
                    help="comma-separated contexts: a decode point each, every "
                         "lane at that context (instead of the cell's own)")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--form", default="kernel", choices=["kernel", "gather"],
                    help="gather: time the plain-XLA form instead (per-head "
                         "cells without a window or a sink)")
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--root", default="",
                    help="another checkout to import arkflow_tpu from (a parent "
                         "commit unpacked beside this one)")
    args = ap.parse_args()
    unknown = set(args.cell.split(",")) - {"all", *CELLS}
    if unknown:
        ap.error(f"--cell: no such cell: {sorted(unknown)}")
    if args.root:
        sys.path.insert(0, args.root)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from arkflow_tpu.ops import ragged_attention as ra

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.interpret:
        print("found no TPU: a time from the CPU says nothing (--interpret "
              "rehearses tiny shapes)", file=sys.stderr)
        return 1
    peak = None
    if device.platform == "tpu":
        with open(__file__.rsplit("/", 2)[0] + "/benchmark/peaks.json") as f:
            peak = json.load(f)[device.device_kind]["hbm_bytes_per_s"]
    ceilings = [int(g) for g in args.group_max.split(",") if g] or [None]
    rng = np.random.RandomState(args.seed)
    held_for = None
    for name, cell, kind, off in _points(args):
        if held_for != name:  # one cell's pools on the device at a time
            k = v = sink = None
            k, v, sink, lanes, cols = _pools(cell, args.seed, args.interpret,
                                             hasattr(ra, "_latent_group"),
                                             hasattr(ra, "narrow_run"))
            held_for = name
        q, table, ctx = _queries(cell, kind, off, rng, lanes, cols, args.interpret,
                                 args.table == "runs")
        latent = "lat" in cell
        b, c, h, _ = (q[0] if latent else q).shape
        window = cell.get("window", 0)
        for ceiling, run in itertools.product(
                ceilings, [int(r) for r in args.run.split(",") if r] or [None]):
            if run is not None and hasattr(ra, "PAGE_RUN"):  # one that takes runs
                ra.PAGE_RUN = run
            if ceiling:
                ra._PAGED_GROUP_MAX = ra._PAGED_CHUNK_GROUP_MAX = ceiling
                ra._PAGED_ONE_HEAD_GROUP_MAX = ceiling
                ra._NARROW_GROUP_MAX = ceiling
                if hasattr(ra, "_latent_group"):  # the walk's group, a window's too
                    ra._latent_group = lambda *_, ceiling=ceiling: ceiling
                else:  # the grid's pages a step
                    ra._LATENT_GROUP = ceiling

            def call(layer, q, k, v, table, ctx, sink):
                if latent:
                    q_lat, q_rope, *allowed = q
                    return ra.mla_paged_attention.__wrapped__(
                        q_lat, q_rope, k, v, layer, table, ctx,
                        scale=(128 + cell["rope"]) ** -0.5, window=window,
                        interpret=args.interpret, allowed=(allowed or [None])[0])
                if args.form == "gather":
                    return _gathered(q, k, v, layer, table, ctx, cell)
                if cell.get("split"):  # as ``paged_decode._attend_paged``
                    g = h // cell["kvh"]
                    return jnp.concatenate([
                        ra.paged_flash_attention.__wrapped__(
                            q[:, :, j * g:(j + 1) * g], k, v, layer + 2 * j,
                            table, ctx, interpret=args.interpret)
                        for j in range(cell["kvh"])], axis=2)
                return ra.paged_flash_attention.__wrapped__(
                    q, k, v, layer, table, ctx, interpret=args.interpret,
                    window=window, sink=sink)

            def many(reps, *operands):  # the pools are arguments, not constants
                def body(i, acc):
                    return acc + call(i % 2, *operands).astype(jnp.float32)
                return jax.lax.fori_loop(0, reps, body, jnp.zeros(
                    (b, c, h, cell["lat" if latent else "dv"]), jnp.float32))

            operands = (q, k, v, table, ctx, sink)
            line = {"cell": name, "kind": kind, "lanes": b, "chunk": c,
                    "offset": off, "ctx_mean": float(ctx.mean()),
                    **({"form": "gather"} if args.form == "gather" else {}),
                    **({"group_max": ceiling} if ceiling else {}),
                    **({"table": args.table, "run": ra.PAGE_RUN}
                       if hasattr(ra, "PAGE_RUN") else {}),
                    "device": device.device_kind}
            if latent and hasattr(ra, "_latent_group"):  # a checkout that walks
                tile_c = ra.latent_query_tile(c, h, cell["lat"])
                line.update(tile_c=tile_c, tiles=-(-c // tile_c), group=ra._latent_group(
                    tile_c, h, PAGE, cell["lat"], v.shape[-1], 2, window))
            elif not latent and k.ndim == 4:  # row-major: the narrow-head walk
                tile_c = ra.query_tile(c, h)
                run = ra.narrow_run(cell["kvh"], cell["dk"])
                hpr = run // cell["dk"] * (h // cell["kvh"])
                line.update(tile_c=tile_c, tiles=-(-c // tile_c), run=run,
                            group=ra._narrow_group(
                                -(-tile_c * hpr // 16) * 16, k.shape[-1] // run,
                                PAGE, k.shape[-1], 2, window, tile_c))
            elif not latent and hasattr(ra, "per_kv_head"):  # cuts tiles per head
                # a call's heads: a K/V head's own where the pools hold a head a layer
                hc, kc = (h // cell["kvh"], 1) if cell.get("split") else (h, cell["kvh"])
                tile_c = ra.query_tile(c, hc)
                per_head = ra.per_kv_head(tile_c, hc, kc)
                line.update(
                    tile_c=tile_c, tiles=-(-c // tile_c), per_kv_head=per_head,
                    group=ra._page_group(
                        tile_c * hc, PAGE, kc, 128 * -(-cell["dk"] // 128),
                        2, cell["dv"], per_head=per_head))
            if args.interpret:
                jax.block_until_ready(jax.jit(call)(1, *operands))
            got = jax.jit(call)(1, *operands) if args.check else None
            if args.check and getattr(ra, "PAGE_RUN", 0):
                # the walk without runs, the same call else: a stretch moves
                # the bytes its pages' copies moved
                ra.PAGE_RUN, run_was = 0, ra.PAGE_RUN
                line["same_as_run_0"] = bool(
                    (got == jax.jit(call)(1, *operands)).all())
                ra.PAGE_RUN = run_was
            if args.check and not (window or sink is not None
                                   or cell.get("split")):
                # a ring's table and a sink have no plain twin here: the
                # tests hold them (tests/test_paged_kernel.py)
                want = (_latent_reference if latent else _reference)(
                    q, k, v, table, ctx, cell)
                line["max_abs_err"] = float(
                    jnp.abs(got.astype(jnp.float32) - want).max())
            if args.interpret:
                line["rehearsal"] = True
            else:
                timed = jax.jit(many, static_argnums=0)
                jax.block_until_ready(timed(args.reps, *operands))
                best = float("inf")
                for _ in range(args.rounds):
                    t0 = time.perf_counter()
                    jax.block_until_ready(timed(args.reps, *operands))
                    best = min(best, time.perf_counter() - t0)
                us = best / args.reps * 1e6
                need = _held_bytes(cell, np.asarray(ctx), c)
                line.update(us_per_call=round(us, 1), held_mb=round(need / 1e6, 2),
                            held_roof_pct=round(need / peak / (us * 1e-6) * 100, 2))
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
