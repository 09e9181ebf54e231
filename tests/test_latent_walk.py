"""The latent kernel's own walk (``ops/ragged_attention.mla_paged_attention``
since PR 44: a (row, query tile) program copies its row's live pages itself,
in groups, a group ahead) against the plain-XLA gather form
(``paged_decode._masked_latent_attention`` over the row's keys read through
the table), interpreted on the CPU. The groups are held to a few pages so
that tiny tables walk several; every pool page no live key sits on is NaN, and
so is a slot's VMEM before its first copy in interpret mode: a dead page that
reached a product, or a dead column of the last group that met a probability
of 0 unzeroed, shows as NaN."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arkflow_tpu.models.paged_decode import (_held_lanes, _masked_latent_attention,
                                             cache_spec)
from arkflow_tpu.ops import ragged_attention as ra

PAGE, LAT, ROPE, HEADS = 8, 16, 4, 4
SCALE = float(8 + ROPE) ** -0.5


def _case(seed, off, c, cols, window=0, heads=HEADS, lat=LAT, lay=None):
    """Queries, pools and a table of ``cols`` columns a row for rows whose
    first query sits at ``off[b]``: non-contiguous pages (or as ``lay`` lays
    the pool's pages out), layer 1 of two, the rope keys as held; pages no
    attendable key sits on are NaN."""
    rng = np.random.RandomState(seed)
    b = len(off)
    n_pages = 1 + b * cols
    table = 1 + (rng.permutation(b * cols) if lay is None else lay(rng, b * cols)
                 ).reshape(b, cols)
    table[np.asarray(off) == 0] = 0  # an idle lane: the scratch page
    cp = rng.normal(0, 1, (2, n_pages, PAGE, lat)).astype(np.float32)
    rp = np.zeros((2, n_pages, PAGE, _held_lanes(ROPE)), np.float32)
    rp[..., :ROPE] = rng.normal(0, 1, (2, n_pages, PAGE, ROPE))
    live = np.zeros((n_pages,), bool)
    live[0] = True
    for r, o in enumerate(off):
        first = max(o - (window - 1), 0) // PAGE if window else 0
        pages = np.arange(first, (o + c - 1) // PAGE + 1)
        live[table[r, pages % cols if window else np.minimum(pages, cols - 1)]] = True
    cp[:, ~live] = np.nan
    rp[:, ~live] = np.nan
    cp[0], rp[0] = np.nan, np.nan  # another layer's rows
    q_lat = rng.normal(0, 1, (b, c, heads, lat)).astype(np.float32)
    q_rope = rng.normal(0, 1, (b, c, heads, ROPE)).astype(np.float32)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)  # noqa: E731
    return (bf(q_lat), bf(q_rope), bf(cp), bf(rp), jnp.asarray(table, jnp.int32),
            jnp.asarray(off, jnp.int32))


def _reference(q_lat, q_rope, cp, rp, table, off, window=0, allowed=None):
    """Every key up to the last query read through the table (a ring's
    column of logical page i is i % columns), masked as the kernel's
    docstring states, in plain XLA."""
    b, c = q_lat.shape[:2]
    cols = table.shape[1]
    n = -(-(int(off.max()) + c) // PAGE) * PAGE
    if allowed is not None:
        n = allowed.shape[-1]
    s = np.arange(n)
    col = (s // PAGE) % cols if window else np.minimum(s // PAGE, cols - 1)
    phys = np.asarray(table)[:, col]
    cc = jnp.nan_to_num(cp[1, phys, s % PAGE])
    rr = jnp.nan_to_num(rp[1, phys, s % PAGE, :ROPE])
    pos = np.asarray(off)[:, None] + np.arange(c)[None, :]
    mask = s[None, None, :] <= pos[..., None]
    if window:
        mask &= s[None, None, :] > pos[..., None] - window
    if allowed is not None:
        mask &= np.asarray(allowed) > 0
    return _masked_latent_attention(q_lat, q_rope, cc, rr,
                                    jnp.asarray(mask)[:, None], SCALE)


def _walk(monkeypatch, group, *operands, **kw):
    monkeypatch.setattr(ra, "_LATENT_WALK_MAX", group)
    return ra.mla_paged_attention.__wrapped__(
        *operands[:4], 1, *operands[4:], scale=SCALE, interpret=True, **kw)


def _close(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


#: (first query positions, queries a row, table columns, pages a group)
WALKS = {
    # decode rows of unequal length, an idle lane at 0 between them
    "decode_unequal_rows_an_idle_lane": ([37, 0, 8, 71, 1], 1, 10, 3),
    # a chunk whose offset and length are no multiple of the group's keys
    "chunk_offset_and_length_off_the_group": ([29], 11, 6, 2),
    # the walk ends one page into its last group of four
    "walk_ends_mid_group": ([32], 1, 9, 4),
    # seven columns in groups of three: the last group is short of the table
    "table_width_no_multiple_of_the_group": ([50], 6, 7, 3),
    # one group holds the whole table
    "one_group": ([13, 20], 1, 3, 32),
}


@pytest.mark.parametrize("case", sorted(WALKS))
def test_the_walk_matches_the_gather_form(monkeypatch, case):
    off, c, cols, group = WALKS[case]
    operands = _case(len(case), off, c, cols)
    _close(_walk(monkeypatch, group, *operands), _reference(*operands))


def _blocks(run, keep=lambda s: True, ascending=False):
    """A layout for ``_case``: the pool cut into blocks of ``run`` neighbours,
    a block an aligned stretch of the (flattened) table, the blocks in any
    order (``ascending``: in the pool's, a row one long run); stretch ``s``
    holds its block as it lies where ``keep(s)``, back to front (no two of
    its entries consecutive upwards) where not."""
    def lay(rng, n):
        order = np.arange(n // run) if ascending else rng.permutation(n // run)
        pages = order[:, None] * run + np.arange(run)
        turned = [s for s in range(len(pages)) if not keep(s)]
        pages[turned] = pages[turned, ::-1]
        return pages.reshape(-1)
    return lay


#: (first query positions, queries a row, table columns, pages a group, pages
#: a run, the layout of the table; a window). 8 keys a page
RUNS = {
    # every aligned stretch of a row's table is one copy
    "all_runs": ([37, 90, 8], 1, 12, 4, 2, _blocks(2), 0),
    # the file's permuted tables: every stretch a page at a time, as before
    "no_runs": ([37, 90, 8], 1, 12, 4, 2, None, 0),
    # every other stretch lies back to front
    "mixed": ([61, 90], 3, 12, 8, 4, _blocks(4, lambda s: s % 2 == 0), 0),
    # position 37 sits on page 4 of a stretch of pages 4-7: pages 5-7 are
    # NaN in the pool, side by side with page 4, and are not read
    "a_run_across_the_walks_last_live_page": ([37, 75], 1, 12, 8, 4, _blocks(4), 0),
    # a row is ONE run of 12 neighbours over groups of 4 pages: a stretch
    # ends where its group does, the next group starts the next copy
    "a_run_across_a_groups_edge": ([90, 70], 2, 12, 4, 4,
                                   _blocks(4, ascending=True), 0),
    # a lane at 0: its table row is the scratch page, its walk one page
    "an_idle_lane_on_the_scratch_page": ([0, 50, 0], 1, 8, 4, 2, _blocks(2), 0),
    # a ring under a window is walked as before whatever its pages are
    "a_window_ring_takes_no_runs": ([85, 3, 40], 1, 4, 32, 2, _blocks(2), 19),
}


@pytest.mark.parametrize("case", sorted(RUNS))
def test_a_table_in_runs_is_walked_a_stretch_a_copy(monkeypatch, case):
    """The walk over tables whose aligned stretches of ``PAGE_RUN`` entries
    name neighbours in the pool (one copy a pool a stretch), over tables
    without, and over both in one row, against the gather form: every table
    gives the plain form's answers, and no page past a row's last live one
    is read (they are NaN; a copied one would show)."""
    off, c, cols, group, run, lay, window = RUNS[case]
    monkeypatch.setattr(ra, "PAGE_RUN", run)
    operands = _case(len(case), off, c, cols, window=window, lay=lay)
    table, walked = np.asarray(operands[4]), np.asarray(off) // PAGE + 1
    taken = ra.pages_in_runs(table, walked)
    assert (taken == 0) == (lay is None)
    if case == "a_run_across_the_walks_last_live_page":
        assert taken == 4 + 8 and np.isnan(np.asarray(
            operands[2], np.float32)[1, table[0, 5:8]]).all()
    _close(_walk(monkeypatch, group, *operands, window=window),
           _reference(*operands, window=window))


def test_the_walk_takes_runs_only_where_a_stretch_fits():
    """One copy moves ``PAGE_RUN`` pages of a group: a group that is no
    multiple of it, a ring under a window and a pool smaller than a stretch
    are walked a page at a time, the kernel as it was (no test of the table
    in it); elsewhere a stretch's copy carries ``PAGE_RUN`` pages."""
    def kernel_text(group, window=0, pages=40):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(ra, "_LATENT_WALK_MAX", group)
            q = jnp.zeros((2, 1, HEADS, LAT), jnp.bfloat16)
            return str(jax.make_jaxpr(lambda *a: ra.mla_paged_attention.__wrapped__(
                *a, scale=SCALE, window=window))(
                q, q[..., :ROPE], jnp.zeros((2, pages, PAGE, LAT), jnp.bfloat16),
                jnp.zeros((2, pages, PAGE, 128), jnp.bfloat16), 1,
                jnp.zeros((2, 16), jnp.int32), jnp.zeros((2,), jnp.int32)))

    assert ra.PAGE_RUN == 8
    plain = [kernel_text(12), kernel_text(8, window=19), kernel_text(8, pages=7)]
    assert len({text.count("cond[") for text in plain}) == 1
    # the test of a stretch's entries, where the first group is started and
    # where the next one is
    assert kernel_text(8).count("cond[") == plain[0].count("cond[") + 2


@pytest.mark.parametrize("tile", ["one_tile", "tiles_of_8"])
def test_a_chunk_cut_into_query_tiles_pads_its_queries_past_the_table(
        monkeypatch, tile):
    """20 queries of 128 heads are three tiles of 8, the last padded with 4
    queries that sit past the table's last column: its walk stops at the
    table's width."""
    heads = 128 if tile == "tiles_of_8" else HEADS
    operands = _case(3, [44], 20, 8, heads=heads)
    _close(_walk(monkeypatch, 3, *operands), _reference(*operands))


@pytest.mark.parametrize("case,off,c", [
    ("decode_wrapped_and_short", [85, 3, 40], 1),   # the ring wrapped twice / not yet
    ("chunk_across_the_wrap", [43], 9),
    ("chunk_from_0", [0], 9)])
def test_the_ring_wraps_under_a_window(monkeypatch, case, off, c):
    """A window of 19 keys over a ring of (19 + c - 2) // 8 + 2 pages: the
    walk starts at the page of the first query's oldest key and reads
    column i % columns; a group is the tile's whole walk."""
    window = 19
    cols = (window + c - 2) // PAGE + 2
    operands = _case(len(case), off, c, cols, window=window)
    _close(_walk(monkeypatch, 32, *operands, window=window),
           _reference(*operands, window=window))


@pytest.mark.parametrize("case", ["context_under_topk", "a_choice_that_skips_pages",
                                  "first_groups_all_refused"])
def test_the_indexers_choice_narrows_the_keys(monkeypatch, case):
    """``allowed`` [B, C, context] beside the causal bound: every seen key
    where the context is under ``index_topk``; a choice that leaves whole
    pages out; a choice whose first groups hold no key at all (what a step
    without any key summed is scaled away when the first key arrives)."""
    off, c, cols = [21, 60], 3, 9
    operands = _case(len(case), off, c, cols)
    pos = np.asarray(off)[:, None] + np.arange(c)[None, :]
    seen = np.arange(cols * PAGE)[None, None, :] <= pos[..., None]
    if case == "context_under_topk":
        allowed = seen
    elif case == "a_choice_that_skips_pages":
        allowed = seen & (np.random.RandomState(5).rand(*seen.shape) < 0.3)
        allowed[..., 8:24] = False
        allowed |= np.arange(cols * PAGE)[None, None, :] == pos[..., None]
    else:  # only the last five keys before each query
        allowed = seen & (np.arange(cols * PAGE)[None, None, :] > pos[..., None] - 5)
    allowed = jnp.asarray(allowed, jnp.float32)
    _close(_walk(monkeypatch, 2, *operands, allowed=allowed),
           _reference(*operands, allowed=allowed))


def test_groups_follow_the_shapes_of_the_call():
    """``_latent_group``: whole 128-lane score tiles where a group has that
    many keys, ``_LATENT_WALK_MAX`` pages where a walk is unbounded, a
    tile's whole walk under a window, less where VMEM binds."""
    # dots3_l5: a chunk tile of 8 positions x 128 heads, a decode row
    assert ra.latent_query_tile(512, 128, 512) == 8
    assert ra.latent_query_tile(512, 64, 1024) == 8 and ra.latent_query_tile(1, 64, 1024) == 1
    assert ra._latent_group(8, 128, 16, 512, 128, 2) == ra._LATENT_WALK_MAX == 32
    assert ra._latent_group(1, 128, 16, 512, 128, 2) == 32
    # its sliding layers: 513 + 8 - 2 keys span 34 pages, 40 in whole tiles
    assert ra._latent_group(8, 64, 16, 1024, 128, 2, window=513) == 40
    assert ra._latent_group(1, 64, 16, 1024, 128, 2, window=513) == 40
    assert ra._latent_group(1, 4, 8, 16, 128, 2, window=19) == 16  # page 8: 16 a tile
    assert ra._latent_group(32, 128, 16, 512, 128, 2) == 16         # VMEM binds
    assert ra._latent_group(512, 128, 16, 512, 128, 2) == 1


def test_the_pools_hold_the_rope_key_in_whole_lane_rows():
    """One layout whatever serves: ``cache_spec`` states the rope key as
    held, a multiple of 128 lanes, for the kept and the window pool."""
    from arkflow_tpu.models import decoder as dec

    cfg = dec.DecoderConfig(
        vocab_size=64, dim=32, layers=3, heads=4, ffn=48, max_seq=64,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        rope_interleave=True, n_routed_experts=8, num_experts_per_tok=2,
        n_shared_experts=1, moe_intermediate_size=16, first_k_dense_replace=1)
    (pool,) = cache_spec(cfg)
    assert pool.widths == (16, 128) and pool.bytes_per_token == 3 * 2 * 144
    assert [_held_lanes(w) for w in (1, 64, 128, 129)] == [128, 128, 128, 256]


# -- the server's count of the walk -------------------------------------------------

LATENT = dict(vocab_size=128, dim=32, layers=3, heads=4, ffn=64, max_seq=128,
              rope_theta=1e4, norm_eps=1e-6, kv_lora_rank=16, qk_nope_head_dim=8,
              qk_rope_head_dim=4, v_head_dim=8, rope_interleave=True,
              n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=2,
              moe_intermediate_size=16, first_k_dense_replace=1,
              routed_scaling_factor=2.448)
PATTERN = dict(LATENT, layers=5, n_shared_experts=1,
               layer_types=("full_attention", "full_attention", "sliding_attention",
                            "sliding_attention", "sliding_attention"),
               sliding_window=9, q_lora_rank=12, swa_heads=2, swa_q_lora_rank=12,
               swa_kv_lora_rank=24, swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4,
               swa_v_head_dim=8, swa_rope_theta=5e3, index_n_heads=4,
               index_head_dim=8, index_topk=16, experts_held=(4, 2))


@pytest.mark.parametrize("kernel", ["paged", "gather"])
@pytest.mark.parametrize("layout", ["latent", "pattern"])
def test_a_latent_server_counts_the_pages_its_rows_walk(layout, kernel):
    """``arkflow_gen_attn_pages_walked_total`` / ``_table_columns_total`` for
    a latent model as for a per-head one (kept pool, a layer, from lengths on
    the host): a prompt of 13 tokens in chunks of 8 — their last queries at
    7 and 15: 1 and 2 pages of 8 —, then three decode steps of two lanes at
    lengths 13..15 (2 pages each), the idle lane its one scratch page. No
    per-head tile is counted (one shared head: nothing to cut); a ``gather``
    server walks nothing and counts nothing."""
    import asyncio

    from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded
    from arkflow_tpu.obs import global_registry

    ensure_plugins_loaded()
    proc = build_component("processor", {
        "type": "tpu_generate", "model": "decoder_lm",
        "model_config": LATENT if layout == "latent" else PATTERN,
        "serving": "continuous", "max_input": 36, "max_new_tokens": 4, "slots": 2,
        "page_size": PAGE, "seq_buckets": [8], "prefill_chunk": 8, "eos_id": -1,
        "decode_kernel": kernel, "kernel_interpret": True, "seed": 3}, Resource())
    server = proc._server
    reg = global_registry()

    def counts():
        return {(name, kind): reg.counter(
            f"arkflow_gen_attn_{name}_total",
            labels={"model": "decoder_lm", "kind": kind}).value
            for name in ("pages_walked", "table_columns") for kind in ("decode", "chunk")}

    before = counts()
    out = asyncio.run(server.generate(list(range(1, 14)), 4))
    assert len(out) == 4 and server.decode_kernel == kernel
    got = {k: v - before[k] for k, v in counts().items()}
    assert not server.m_attn_tiles
    if kernel == "gather":
        assert not server.m_attn_walk and not any(got.values())
        return
    cols = server.pages_per_slot
    assert cols == 5                              # 40 positions of 8 a page
    assert (got["pages_walked", "chunk"], got["table_columns", "chunk"]) == (
        1 + 2, 2 * cols)
    assert (got["pages_walked", "decode"], got["table_columns", "decode"]) == (
        3 * (2 + 1), 3 * 2 * cols)


def test_a_latent_server_counts_its_runs_and_uploads_nothing_for_them():
    """A prompt of 70 tokens in chunks of 8 and four new tokens over 13
    columns of 8 keys: the slot's first eight pages are one block of the
    pool (``_FreePages``), so every step whose row walks eight pages or more
    moves its first stretch as one copy — the chunks that end at 63 and 71,
    the three decode steps at 70..72 — and
    ``arkflow_gen_attn_pages_in_runs_total`` counts eight pages each, by the
    kernel's predicate over the table the step carries anyway: every step
    still hands the device ONE host array (``gen_uploads_per_step`` 1.0)."""
    import asyncio

    from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded
    from arkflow_tpu.obs import global_registry

    ensure_plugins_loaded()
    server = build_component("processor", {
        "type": "tpu_generate", "model": "decoder_lm", "model_config": LATENT,
        "serving": "continuous", "max_input": 100, "max_new_tokens": 4, "slots": 2,
        "page_size": PAGE, "seq_buckets": [8], "prefill_chunk": 8, "eos_id": -1,
        "decode_kernel": "paged", "kernel_interpret": True, "seed": 3,
        "dispatch_depth": 1}, Resource())._server
    assert ra.PAGE_RUN == 8 and server.pages_per_slot == 13  # 104 positions
    reg = global_registry()

    def counts():
        return {kind: (reg.counter("arkflow_gen_attn_pages_in_runs_total", labels={
            "model": "decoder_lm", "kind": kind}).value, server.m_uploads[kind].value)
            for kind in ("decode", "chunk")}

    steps = dict.fromkeys(("decode", "chunk"), 0)
    for kind in steps:
        def counted(*args, _fn=getattr(server, "_" + kind), _kind=kind):
            steps[_kind] += 1
            return _fn(*args)
        setattr(server, "_" + kind, counted)
    before = counts()
    out = asyncio.run(server.generate(list(range(1, 71)), 4))
    got = {k: (a - before[k][0], b - before[k][1]) for k, (a, b) in counts().items()}
    assert len(out) == 4 and steps == {"decode": 3, "chunk": 9}
    assert got == {"chunk": (8 + 8, 9), "decode": (3 * 8, 3)}
