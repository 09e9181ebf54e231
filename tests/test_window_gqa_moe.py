"""A layer pattern of per-head (GQA) layers — sliding-window layers beside
full layers — with a held share of routed experts (the K-EXAONE layout),
through the paged serving path, held to the plain reference ``benchmark/
references/window_gqa_moe.py`` on seeded weights at tiny widths: a window of
9 over pages of 8, so 26- to 58-token rows pass the window, wrap their ring
of window pages and have chunk boundaries inside a window, and 4 of 16
experts held (Pallas in interpret mode).

The equations are held EXACTLY: with the program's products switched to
float32 (``exact``) its logits are the reference's to 2e-4 through the full
forward and through chunked prefill and decode over the cache, choices
included. The bfloat16 program is held kernel by kernel to its plain-XLA
form (the build-time probe) and, served, to the reference's judge.
"""

from __future__ import annotations

import asyncio
import dataclasses
import hashlib
import importlib.util
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded
from arkflow_tpu.errors import ConfigError
from arkflow_tpu.models import common as cm
from arkflow_tpu.models import decoder as dec
from arkflow_tpu.models.paged_decode import (_attend_paged, _attend_ring,
                                             cache_spec, gqa_kernel_probe,
                                             init_page_pool, kv_bytes_per_token,
                                             paged_decode_step, paged_prefill,
                                             paged_prefill_chunk,
                                             window_ring_pages)
from arkflow_tpu.obs import global_registry

ensure_plugins_loaded()


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/references/window_gqa_moe.py", "ref_window_gqa_moe")

FULL, SLIDING = dec.FULL, dec.SLIDING
TINY = dict(vocab_size=128, dim=32, layers=5, heads=4, kv_heads=2, head_dim=8,
            ffn=64, max_seq=256, rope_theta=1e4, norm_eps=1e-5,
            n_routed_experts=16, num_experts_per_tok=4, n_shared_experts=1,
            moe_intermediate_size=16, first_k_dense_replace=1,
            routed_scaling_factor=2.5, experts_held=(4, 4),
            # longer than ``layers``, as a published list cut in depth is
            layer_types=(SLIDING, SLIDING, SLIDING, FULL, SLIDING, SLIDING, SLIDING, FULL),
            sliding_window=9, qk_norm=True, full_attention_rope=False)
CFG = dec.DecoderConfig(**TINY)
PAGE = 8
INTERPRET = dict(attention_kernel="paged", kernel_interpret=True)
KERNELS = pytest.mark.parametrize("kern", [{}, INTERPRET], ids=["gather", "paged"])


def _round_like_placed(params, cfg):
    return jax.tree_util.tree_map(
        lambda leaf, dt: leaf.astype(dt).astype(jnp.float32), params,
        dec.serve_dtypes(cfg))


@pytest.fixture(scope="module")
def params():
    """Seeded weights; the selection bias at +-0.05, the size of the gaps
    between 16 experts' scores, and head-norm scales off 1."""
    p = dec.init(jax.random.PRNGKey(3), CFG)
    p["layers"]["router_bias"] = jax.random.uniform(
        jax.random.PRNGKey(8), p["layers"]["router_bias"].shape, jnp.float32,
        -0.05, 0.05)
    for name in ("dense_layers", "layers"):
        for i, norm in enumerate(("q_head_norm", "k_head_norm")):
            p[name][norm]["scale"] = jax.random.uniform(
                jax.random.PRNGKey(11 + i), p[name][norm]["scale"].shape,
                jnp.float32, 0.5, 1.5)
    return _round_like_placed(p, CFG)


def _reference(params, ids, cfg=CFG):
    """Reference logits [S, vocab] over one row."""
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(lambda p, x: ref.decoder_logits(
            p, x, 0, new=len(ids), hp=ref.hyper(cfg))[0])
        return np.asarray(fn(params, jnp.asarray(ids)))


@pytest.fixture
def exact(monkeypatch):
    """The program's products in float32 at ``highest`` precision: what is
    left between it and the reference is the order of float32 sums."""
    monkeypatch.setattr(cm.dense, "__defaults__", (jnp.float32,))
    monkeypatch.setattr(cm.embedding, "__defaults__", (jnp.float32,))
    with jax.default_matmul_precision("highest"):
        yield


EXACT = 2e-4
IDS = np.random.RandomState(5).randint(1, 128, 60).astype(np.int32)


def _tables(cfg, rows: int, pages_per: int, step: int):
    """Non-contiguous kept tables and, for the window pool, every row's ring
    filled with pages of its own (page 0 is the scratch page of both)."""
    kept = np.random.RandomState(2).permutation(
        np.arange(1, 1 + rows * pages_per)).reshape(rows, pages_per)
    cols = window_ring_pages(cfg, PAGE, step)
    ring = np.random.RandomState(3).permutation(
        np.arange(1, 1 + rows * cols)).reshape(rows, cols)
    return (jnp.asarray(kept, jnp.int32), jnp.asarray(ring, jnp.int32)), cols


# -- the cache spec and the layer runs -------------------------------------------


def test_cache_spec_states_kept_rows_and_window_rows():
    pools = {p.name: p for p in cache_spec(CFG)}
    assert list(pools) == ["kv", "kv_window"]
    kv = CFG.kv_heads * CFG.dh
    assert pools["kv"].layers == 1 and pools["kv_window"].layers == 4
    assert pools["kv"].widths == pools["kv_window"].widths == (kv, kv)
    assert pools["kv"].window == 0 and pools["kv_window"].window == 9
    assert kv_bytes_per_token(CFG) == 5 * 2 * kv * 2
    kp, vp = init_page_pool(CFG, 7, PAGE, window_pages=5)
    assert set(kp) == set(vp) == {"kv", "kv_window"}
    # heads of 8, narrower than 128 lanes: row-major, a token's side by side
    assert kp["kv"].shape == (1, 7, PAGE, 2 * 8) and kp["kv"].dtype == jnp.bfloat16
    assert vp["kv_window"].shape == (4, 5, PAGE, 2 * 8)
    assert pools["kv"].row_major and pools["kv_window"].row_major
    wide = {p.name: p for p in cache_spec(dataclasses.replace(CFG, head_dim=128))}
    assert not wide["kv"].row_major
    assert wide["kv_window"].shapes(5, PAGE) == [(4, 5, PAGE, 2, 128)] * 2
    # a ring holds the window before a step's first query to its last
    assert window_ring_pages(CFG, PAGE, 1) == 3
    assert window_ring_pages(CFG, PAGE, 8) == 3
    assert window_ring_pages(CFG, PAGE, 12) == 4
    plain = dataclasses.replace(CFG, layer_types=None, sliding_window=0)
    assert [p.name for p in cache_spec(plain)] == ["kv"]
    assert window_ring_pages(plain, PAGE, 8) == 0


def test_layer_runs_are_runs_of_a_kind_within_dense_and_routed_stacks():
    assert dec.layer_runs(CFG) == [
        ("dense_layers", 0, 1, SLIDING, False, 0),
        ("layers", 0, 2, SLIDING, True, 1),
        ("layers", 2, 3, FULL, True, 0),
        ("layers", 3, 4, SLIDING, True, 3)]
    plain = dec.DecoderConfig(vocab_size=64, dim=32, layers=3, heads=4,
                              kv_heads=2, ffn=64)
    assert dec.layer_runs(plain) == [("layers", 0, 3, FULL, False, 0)]
    assert not plain.by_runs and CFG.by_runs and CFG.layered


def test_serve_dtypes_cover_every_leaf_and_state_the_choosers_float32():
    masters = dec.init(jax.random.PRNGKey(3), CFG)
    dtypes = dec.serve_dtypes(CFG)
    assert (jax.tree_util.tree_structure(masters)
            == jax.tree_util.tree_structure(dtypes))
    for path, dt in jax.tree_util.tree_flatten_with_path(dtypes)[0]:
        keys = [str(getattr(k, "key", k)) for k in path]
        chooser = any("router" in k or "norm" in k for k in keys)
        assert (dt == jnp.float32) == chooser, keys
    assert jax.tree_util.tree_structure(dec.param_specs(CFG, {})) == \
        jax.tree_util.tree_structure(dtypes)
    assert masters["layers"]["experts"]["w_gate"].shape == (4, 5, 32, 16)


# -- the equations -----------------------------------------------------------------


def test_forward_matches_reference(params, exact):
    got = np.asarray(dec.forward(params, CFG, jnp.asarray(IDS)[None]))[0]
    np.testing.assert_allclose(got, _reference(params, IDS), atol=EXACT)


@pytest.mark.parametrize("ablation", ["window", "rope_on_full", "no_qk_norm", "bias"])
def test_reference_comparison_detects(params, exact, ablation):
    """The comparison sees each thing the configuration assumes."""
    cfg, p = CFG, params
    if ablation == "window":
        cfg = dataclasses.replace(CFG, sliding_window=10)
    elif ablation == "rope_on_full":
        cfg = dataclasses.replace(CFG, full_attention_rope=True)
    elif ablation == "no_qk_norm":
        cfg = dataclasses.replace(CFG, qk_norm=False)
    else:
        p = {**params, "layers": {**params["layers"], "router_bias": jnp.zeros_like(
            params["layers"]["router_bias"])}}
    got = np.asarray(dec.forward(p, cfg, jnp.asarray(IDS)[None]))[0]
    assert np.abs(got - _reference(params, IDS)).max() > 50 * EXACT


def _through_the_cache(params, rows, lens, new, chunk, kern, pages_per=8):
    """Chunked prefill of three ragged rows, then lockstep decode steps fed
    the rows' own tokens: every step's logits, a row at a time, and the
    counters of each chunk and decode step. Pools float32 (``exact``)."""
    (kept, ring), cols = _tables(CFG, 3, pages_per, chunk)
    kp, vp = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        init_page_pool(CFG, 1 + 3 * pages_per, PAGE, 1 + 3 * cols))
    chunked = jax.jit(lambda p, *a: paged_prefill_chunk(p, CFG, *a, **kern))
    step = jax.jit(lambda p, *a: paged_decode_step(
        p, CFG, *a, return_logits=True, **kern))
    got, counts = [[] for _ in lens], []
    for r, n in enumerate(lens):
        for off in range(0, n, chunk):
            c = rows[r][off:min(off + chunk, n)]
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :len(c)] = c
            logits, kp, vp, stats = chunked(
                params, jnp.asarray(ids), jnp.asarray([off]),
                jnp.asarray([len(c)]), (kept[r:r + 1], ring[r:r + 1]), kp, vp)
            counts.append((len(c), [int(v) for v in stats]))
        got[r].append(np.asarray(logits)[0])
    cur = np.asarray(lens, np.int32)
    for i in range(new - 1):
        tok = jnp.asarray([rows[r][lens[r] + i] for r in range(3)])
        logits, kp, vp, stats = step(params, tok, jnp.asarray(cur),
                                     jnp.asarray([True] * 3), (kept, ring), kp, vp)
        counts.append((3, [int(v) for v in stats]))
        for r in range(3):
            got[r].append(np.asarray(logits)[r])
        cur += 1
    return [np.stack(g) for g in got], counts


LENS, NEW = [41, 26, 53], 5
ROWS = [np.random.RandomState(21 + r).randint(1, 128, n + NEW).astype(np.int32)
        for r, n in enumerate(LENS)]


@KERNELS
@pytest.mark.parametrize("chunk", [8, 12])
def test_chunked_prefill_then_decode_matches_reference(params, exact, chunk, kern):
    """Rows of 41, 26 and 53 tokens pass the window (9) and wrap their ring
    (3 or 4 pages of 8); chunks of 8 and of 12 put chunk boundaries inside a
    window and off the page grid. The logits of every step are the
    reference's full-forward logits and the counters a hand count, through
    plain XLA and through the Pallas kernels (the window's lower bound over
    the ring, the expert product)."""
    got, counts = _through_the_cache(params, ROWS, LENS, NEW, chunk, kern)
    for r, n in enumerate(LENS):
        want = _reference(params, ROWS[r][:n + NEW - 1])
        np.testing.assert_allclose(got[r], want[n - 1:], atol=EXACT)
    for n, (pairs, hit, load, here) in counts:
        assert pairs == n * 4 * 4 and 0 <= here <= pairs
        assert 0 < hit <= 4 * 4 and 0 < load <= n


def test_one_shot_prefill_refuses_a_layer_pattern(params):
    (kept, _), _ = _tables(CFG, 1, 8, 8)
    kp, vp = init_page_pool(CFG, 9, PAGE, 4)
    with pytest.raises(ConfigError, match="kv, kv_window.*prefills in chunks"):
        paged_prefill(params, CFG, jnp.zeros((1, 16), jnp.int32),
                      jnp.asarray([9]), kept, kp, vp)


# -- the windowed kernel against its plain-XLA twin ---------------------------------


def test_kernel_probe_covers_the_pattern_s_kernels(params):
    from arkflow_tpu.tpu.serving_core import logits_parity

    out = gqa_kernel_probe(params, CFG, PAGE, kernel_interpret=True)
    assert [name for name, _, _ in out] == [
        "paged_attention_decode", "paged_attention_chunk",
        "paged_window_attention_decode", "paged_window_attention_chunk",
        "expert_product"]
    for name, want, got in out:
        assert logits_parity(want, got)["ok"], name


@pytest.mark.parametrize("window,page,c,offs", [
    (9, 8, 1, (0, 7, 30, 101)), (9, 8, 12, (0, 5, 40, 99)),
    (16, 8, 24, (0, 16, 33, 64)), (128, 16, 40, (0, 100, 300, 1000)),
    (128, 16, 1, (1, 127, 128, 4000)),
    # the ring wraps INSIDE a group of the kernel's walk: a window of 40 over
    # pages of 4 is a ring of 12 (decode) or 14 (a chunk of 8) columns, which
    # a walk of 8-page groups enters at any column and leaves past the last;
    # and a walk of more than one group, its last partly dead
    (40, 4, 1, (3, 39, 57, 200, 1001)), (40, 4, 8, (0, 36, 95, 642, 3001)),
    (70, 4, 1, (68, 69, 70, 333, 1702))])
@pytest.mark.parametrize("walker", ["kernel", "row_major"])
def test_windowed_kernel_matches_its_plain_xla_twin(walker, window, page, c, offs):
    """Rows at their start, inside their first window, past it and past the
    ring's wrap, each ring holding only the pages a server would hold. By
    the walk over pools [.., kv heads, width] (a head of 128 lanes' on a
    chip), and by the walk over row-major pools, which a narrower head's
    are."""
    cfg = dataclasses.replace(CFG, sliding_window=window)
    cols = window_ring_pages(cfg, page, c)
    b = len(offs)
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 4))
    rand = lambda shape: jax.random.normal(next(keys), shape, jnp.float32)  # noqa: E731
    kp, vp = rand((2, 1 + b * cols, page, 2, 8)), rand((2, 1 + b * cols, page, 2, 8))
    # pages the window has passed are another row's by now (or scratch)
    ring = np.zeros((b, cols), np.int32)
    for r, off in enumerate(offs):
        oldest = max(off - (window - 1), 0) // page
        for i in range(oldest, (off + c - 1) // page + 1):
            ring[r, i % cols] = 1 + r * cols + i % cols
    ring, off = jnp.asarray(ring), jnp.asarray(offs, jnp.int32)
    q = rand((b, c, 4, 8))
    positions = off[:, None] + jnp.arange(c)[None, :]
    want = _attend_ring(q, kp, vp, 1, ring, positions, window)
    if walker == "row_major":
        kp, vp = (a.reshape(*a.shape[:3], -1) for a in (kp, vp))
        np.testing.assert_array_equal(
            np.asarray(_attend_ring(q, kp, vp, 1, ring, positions, window)),
            np.asarray(want))
    got = _attend_paged(q, kp, vp, 1, ring, off, cfg, None, True, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def _jaxpr_text(jaxpr) -> str:
    """A jaxpr's text without source positions and addresses."""
    return re.sub(r"0x[0-9a-f]+", "0x", re.sub(r" at [^\s\]]+:\d+", "", str(jaxpr)))


def _text_hash(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


#: sha256 (first 16 hex) of the jaxprs (source positions stripped) of a decode
#: step and a prefill chunk at tiny dense (the Mistral layout) and hybrid (the
#: Falcon-H1 layout) sizes. At a head of 128 lanes — what the benchmark's
#: per-head cells serve — ``walk`` is the kernel walking the page table itself
#: (``window`` 0 still lowers to ONE form of each), a decode step as PR 41
#: left it, a chunk as PR 43 did (its tile multiplies a K/V head at a time),
#: and ``gather_wide`` the plain-XLA form, recorded at PR 46's parent (which
#: reproduces all eight): PR 46 moved none of them. ``gather`` and ``paged``
#: are a head of 64, RE-RECORDED at PR 46 on purpose: a head narrower than
#: 128 lanes has row-major pools and the narrow-head walk since (until then
#: these stood for the GRID's walk at a head of 8, 48f260fdc014f61e ..
#: 31135aa3fc6bb0df, which went with the grid)
#: PR 60 RE-RECORDED the four ``walk`` entries: it changed the
#: kernel's body on purpose (both products take the type the pools hold; a
#: chunk tile reads a head's rows out of the slot's own words), so every
#: program that holds ``_paged_kernel`` moved and nothing else did (the gather
#: forms and the narrow head's ``paged`` stand as recorded)
#: PR 61 RE-RECORDED the four ``walk`` entries (a0adb19001bf947b, 5010a46cdf3eff63, c471586378e96771, 8669ac1ba2980a61 before it): its walk is ``_page_walk``'s (a run of ``PAGE_RUN`` neighbours a copy out of pools that
#: ride as flat rows, a program's last step starting the next program's first
#: group): every program that holds ``_paged_kernel`` moved — a window call's
#: too, whose walk takes no runs but shares the copies and the hand-on — and
#: nothing else did
WINDOW_0_GOLDEN = {
    "dense.decode.gather": "0c702f3da9e0d32f", "dense.chunk.gather": "9c8368cf57b10193",
    "dense.decode.paged": "54c9aa64d0a27507", "dense.chunk.paged": "2d700266541cda06",
    "hybrid.decode.gather": "6301e1548c8fe7d1", "hybrid.chunk.gather": "861f1ac755c688c9",
    "hybrid.decode.paged": "58b5a45d283d50e2", "hybrid.chunk.paged": "6387207ad1c67272",
    "dense.decode.gather_wide": "a4fe2d04ebb3b860",
    "dense.chunk.gather_wide": "a6ada1e181277574",
    "hybrid.decode.gather_wide": "652c84eb6ce512e5",
    "hybrid.chunk.gather_wide": "d7b395f58ea511f1",
    "dense.decode.walk": "ca05ec537e8bbabd", "dense.chunk.walk": "8e5362ff59a68efe",
    "hybrid.decode.walk": "12bf7c4f086daba0", "hybrid.chunk.walk": "f014436fe73184fa"}


def _window_0_text(case: str) -> str:
    """The jaxpr's text of ``case`` = layout.step.kernel."""
    layout, step, kern = case.split(".")
    # heads of 64: two K/V heads fill one 128-lane run of a row-major pool
    sizes = dict(vocab_size=64, dim=256, layers=2, heads=4, kv_heads=2, ffn=48,
                 max_seq=64)
    if layout == "hybrid":
        sizes.update(mamba_d_ssm=32, mamba_n_heads=4, mamba_d_head=8,
                     mamba_d_state=8, mamba_n_groups=2)
    if kern in ("walk", "gather_wide"):  # a head of 128 lanes
        sizes.update(dim=256, heads=2, kv_heads=1)
    cfg = dec.DecoderConfig(**sizes)
    p = jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg))
    kp, vp = jax.eval_shape(lambda: init_page_pool(cfg, 9, 8, slots=2))
    table = jnp.zeros((2, 4), jnp.int32)
    kw = dict(attention_kernel={"walk": "paged", "gather_wide": "gather"}.get(
        kern, kern), kernel_interpret=False)
    if step == "decode":
        jaxpr = jax.make_jaxpr(lambda p, k, v: paged_decode_step(
            p, cfg, jnp.zeros((2,), jnp.int32), jnp.ones((2,), jnp.int32),
            jnp.ones((2,), bool), table, k, v, **kw))(p, kp, vp)
    else:
        extra = {"ssm_rows": jnp.asarray([1], jnp.int32)} if cfg.hybrid else {}
        jaxpr = jax.make_jaxpr(lambda p, k, v: paged_prefill_chunk(
            p, cfg, jnp.zeros((1, 8), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.full((1,), 5, jnp.int32), table[:1], k, v, **kw, **extra))(p, kp, vp)
    return _jaxpr_text(jaxpr)


@pytest.mark.parametrize("case", sorted(WINDOW_0_GOLDEN))
def test_window_0_gives_the_present_jaxpr(case):
    """Without a window the layer loop and the kernel trace to what they
    traced to before there were windows: one scan over ``layers``, the
    kernel's call unchanged (Mistral's and Falcon-H1's programs)."""
    text = _window_0_text(case)
    assert case.startswith("hybrid") or text.count("scan[") == 1  # ONE layer loop
    assert _text_hash(text) == WINDOW_0_GOLDEN[case]


#: the same hashes of programs that never call the per-head kernel: a tiny
#: latent model (the kanana2_l6 layout) and a tiny latent layer pattern with
#: indexed full layers and sliding layers (the dots3_l5 layout), through their
#: Pallas kernels. What "their programs are unchanged" means for the cells
#: that bypass a change to ``paged_flash_attention``. RE-RECORDED at PR 44,
#: on purpose: that PR changed THESE programs — the latent kernel walks the
#: table itself (its grid lost the page-group axis) and the rope keys' pools
#: are 128 lanes wide — and left every per-head golden above as it stood:
#: the bypass ran the other way. (Recorded at PR 41's parent, and standing
#: through PR 43: 3a0f9a616b1af93d, c697262a43550287, 4394292e666c84a0,
#: 158c1079bde200c3.) The two ``pattern`` hashes RE-RECORDED at PR 47, on
#: purpose again: an indexed layer's choice is ``dsa_topk_select``'s where it
#: was a sort's (PR 44's: 5ed8445db227631b, f4474904b5691023); the ``latent``
#: two stood — the layout without an indexer never reaches that branch.
#: All four RE-RECORDED at PR 54, on purpose once more: the latent walk moves
#: a whole stretch of ``PAGE_RUN`` neighbours as one copy and its slots are
#: [group, page, width] (PR 47's: ccb2ee94b5d8cfe9, 8f160b67834088ca,
#: fc3e4a6979722248, 4ac205702f34d60a); what that PR had to leave standing
#: is ``WALK_BYPASS_GOLDEN``, below.
BYPASS_GOLDEN = {
    "latent.decode": "fb8bb5e87fc3416b", "latent.chunk": "12309b7328d12d1a",
    "pattern.decode": "095ffd5cfa34a880", "pattern.chunk": "d8c6eb810c5ac125"}


@pytest.mark.parametrize("case", sorted(BYPASS_GOLDEN))
def test_latent_programs_do_not_move_with_the_per_head_kernel(case):
    layout, step = case.split(".")
    sizes = dict(vocab_size=64, dim=32, layers=3, heads=4, ffn=48, max_seq=64,
                 kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
                 v_head_dim=8, rope_interleave=True, n_routed_experts=8,
                 num_experts_per_tok=2, n_shared_experts=1,
                 moe_intermediate_size=16, first_k_dense_replace=1)
    window_pages = 0
    if layout == "pattern":
        sizes.update(layers=4, layer_types=(FULL, SLIDING, SLIDING, FULL),
                     sliding_window=9, q_lora_rank=12, swa_heads=2,
                     swa_q_lora_rank=12, swa_kv_lora_rank=24,
                     swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4,
                     swa_v_head_dim=8, swa_rope_theta=5e3, index_n_heads=4, index_head_dim=8,
                     index_topk=16, experts_held=(4, 2))
        window_pages = 9
    cfg = dec.DecoderConfig(**sizes)
    p = jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg))
    kp, vp = jax.eval_shape(lambda: init_page_pool(cfg, 9, 8, window_pages))
    kw = dict(attention_kernel="paged", kernel_interpret=False)

    def tables(rows, step_tokens):
        kept = jnp.zeros((rows, 4), jnp.int32)
        if layout == "latent":
            return kept
        return kept, jnp.zeros((rows, window_ring_pages(cfg, 8, step_tokens)),
                               jnp.int32)

    if step == "decode":
        jaxpr = jax.make_jaxpr(lambda p, k, v: paged_decode_step(
            p, cfg, jnp.zeros((2,), jnp.int32), jnp.ones((2,), jnp.int32),
            jnp.ones((2,), bool), tables(2, 1), k, v, **kw))(p, kp, vp)
    else:
        jaxpr = jax.make_jaxpr(lambda p, k, v: paged_prefill_chunk(
            p, cfg, jnp.zeros((1, 8), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.full((1,), 5, jnp.int32), tables(1, 8), k, v, **kw))(p, kp, vp)
    text = _jaxpr_text(jaxpr)
    assert "mla_paged_attention" in text or layout == "pattern"
    assert "paged_flash_attention" not in text
    # the served choice is the threshold kernel's: no sort but the router's
    assert ("dsa_topk_select" in text) == (layout == "pattern")
    assert _text_hash(text) == BYPASS_GOLDEN[case], _text_hash(text)


#: the mirror of ``BYPASS_GOLDEN`` for PR 54, which changed the latent walk
#: and ``_page_walk`` under it: the kernels that take no runs — the narrow
#: head's (row-major pools, ``_page_walk``'s other caller) and the per-head
#: one (its copies are its own) — trace to what they traced to at that PR's
#: parent (aa369a0), where these were recorded. A decode tile and a chunk
#: tile each, straight through ``paged_flash_attention``.
#: PR 60 RE-RECORDED the two ``per_head`` entries: it changed the
#: kernel's body on purpose (both products take the type the pools hold; a
#: chunk tile reads a head's rows out of the slot's own words), so every
#: program that holds ``_paged_kernel`` moved and nothing else did: the
#: ``narrow`` two stand as recorded at aa369a0, which is the bypass PR 60 owes
#: PR 61 RE-RECORDED the two ``per_head`` entries (ea922909c22c8d02, 5c5ee50d4b1d3929; the ``narrow`` two stand: ``_page_walk``'s new arguments default to the walk it was before it): its walk is ``_page_walk``'s (a run of ``PAGE_RUN`` neighbours a copy out of pools that
#: ride as flat rows, a program's last step starting the next program's first
#: group): every program that holds ``_paged_kernel`` moved — a window call's
#: too, whose walk takes no runs but shares the copies and the hand-on — and
#: nothing else did
WALK_BYPASS_GOLDEN = {
    "narrow.decode": "2e05c2d8f5550240", "narrow.chunk": "b5498163e2e0b03b",
    "per_head.decode": "e7d4eeaa6d183826", "per_head.chunk": "58d6776638c2a055"}


@pytest.mark.parametrize("case", sorted(WALK_BYPASS_GOLDEN))
def test_per_head_programs_do_not_move_with_the_latent_walk(case):
    from arkflow_tpu.ops.ragged_attention import paged_flash_attention

    kernel, step = case.split(".")
    b, c = (2, 1) if step == "decode" else (1, 8)
    q = jnp.zeros((b, c, 8, 64 if kernel == "narrow" else 128), jnp.bfloat16)
    pool = jnp.zeros((2, 9, 16, 128) if kernel == "narrow" else (2, 9, 16, 2, 128),
                     jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q, k, v, t, o: paged_flash_attention(
        q, k, v, 1, t, o))(q, pool, pool, jnp.zeros((b, 4), jnp.int32),
                           jnp.zeros((b,), jnp.int32))
    text = _jaxpr_text(jaxpr)
    assert "paged_flash_attention" in text
    assert _text_hash(text) == WALK_BYPASS_GOLDEN[case], _text_hash(text)


# -- the held share ---------------------------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips hold 2 of the 16 experts each: their routed parts, with
    the shared expert counted once, are the uncut layer's output, by the
    program and by the reference alike."""
    uncut_cfg = dataclasses.replace(CFG, experts_held=None)
    whole = _round_like_placed(dec.init(jax.random.PRNGKey(3), uncut_cfg), uncut_cfg)
    lp = jax.tree_util.tree_map(lambda a: a[0], whole["layers"])
    y = jax.random.normal(jax.random.PRNGKey(4), (1, 24, CFG.dim), jnp.float32)
    hp = ref.hyper(uncut_cfg)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.routed_experts(lp, y[0], hp)[0])
        np.testing.assert_allclose(
            np.asarray(dec.routed_mlp(lp, y, uncut_cfg)[0])[0], want, atol=5e-5)
        shared = np.asarray(ref._swiglu(
            y[0], *[lp["experts"][k][16] for k in ("w_gate", "w_up", "w_down")]))
        total, loads = np.zeros_like(want), []
        for first in range(0, 16, 2):
            share = dataclasses.replace(CFG, experts_held=(first, 2))
            ex = {k: jnp.concatenate([v[first:first + 2], v[16:]])
                  for k, v in lp["experts"].items()}
            out, load = dec.routed_mlp({**lp, "experts": ex}, y, share)
            total += np.asarray(out, np.float32)[0] - shared
            loads.append(np.asarray(load))
            part = np.asarray(ref.routed_experts(
                {**lp, "experts": ex}, y[0], {**hp, "held": (first, 2)})[0])
            np.testing.assert_allclose(np.asarray(out)[0], part, atol=2e-5)
    np.testing.assert_allclose(total + shared, want, atol=5e-5)
    # every share routes over all 16 and counts the same loads
    assert all((l == loads[0]).all() for l in loads) and loads[0].sum() == 24 * 4


# -- the server ---------------------------------------------------------------------


def _proc(model_config=None, **extra):
    cfg = {"type": "tpu_generate", "model": "decoder_lm",
           "model_config": {**TINY, **(model_config or {})},
           "serving": "continuous", "max_input": 64, "max_new_tokens": 6,
           "slots": 3, "page_size": PAGE, "seq_buckets": [16],
           "prefill_chunk": 8, "eos_id": -1, "decode_kernel": "gather",
           "seed": 3, **extra}
    return build_component("processor", cfg, Resource())


def _counter(name, **labels):
    return global_registry().counter(name, labels={"model": "decoder_lm", **labels})


PROMPTS = [np.random.RandomState(s).randint(1, 128, n).tolist()
           for s, n in ((1, 44), (2, 23), (3, 61))]


def _serve(poison: bool = False, **extra):
    """Three prompts through the server, every call of ``_slide_window``
    watched: the pages it frees (overwritten at once with large values where
    ``poison``), the most window pages a slot ever held, whether a slot's
    kept pages ever shrank while it lived."""
    proc = _proc(**extra)
    server = proc._server
    slide, seen = server._slide_window, {"freed": [], "live": 0, "kept_low": []}

    def sliding(slot, first, last):
        before = dict(server._slot_win[slot])
        held = len(server._slot_pages[slot])
        slide(slot, first, last)
        gone = [before[i] for i in before if i not in server._slot_win[slot]]
        seen["freed"].extend(gone)
        seen["live"] = max(seen["live"], len(server._slot_win[slot]))
        seen["kept_low"].append(len(server._slot_pages[slot]) >= held)
        if poison and gone:
            idx = jnp.asarray(gone)
            for pools in (server.k_pages, server.v_pages):
                pools["kv_window"] = pools["kv_window"].at[:, idx].set(3e4)

    server._slide_window = sliding
    freed0 = server.m_win_freed.value

    async def run():
        return await asyncio.gather(*[server.generate(p, 6) for p in PROMPTS])

    outs = asyncio.run(run())
    return outs, seen, server, server.m_win_freed.value - freed0


def test_window_pages_stay_within_the_ring_and_are_all_freed_at_the_end():
    """Every window page that is freed is at once overwritten in the pool:
    were it read again (or a kept page freed early and reused) the tokens
    would differ from the undisturbed run's. A slot never holds more window
    pages than its ring has columns; at the end both pools are whole."""
    clean, *_ = _serve()
    outs, seen, server, counted = _serve(poison=True)
    assert outs == clean and [len(o) for o in outs] == [6, 6, 6]
    cols = window_ring_pages(CFG, PAGE, 8)
    assert 0 < seen["live"] <= cols == server._win_cols
    # the window passed pages of every prompt: (n + 5 - 9) // 8 each at least
    assert counted == len(seen["freed"]) >= sum((n + 5 - 9) // PAGE for n in (44, 23, 61))
    assert all(seen["kept_low"])
    assert len(server._win_free) == server.num_win_pages - 1 == 3 * cols
    assert len(server._free_pages) == server.num_pages - 1
    assert all(not live for live in server._slot_win)


def test_the_server_runs_ahead_and_serves_the_lockstep_tokens():
    ahead, _, server, _ = _serve()
    lockstep, _, one, _ = _serve(dispatch_depth=1)
    assert server._ahead and server._steps_ahead > 0 and not one._ahead
    assert ahead == lockstep


def test_server_counters_equal_a_hand_count():
    """One prompt of 21 tokens (chunks of 8: 8 + 8 + 5) and 6 new tokens."""
    proc = _proc()
    server = proc._server
    names = ("arkflow_gen_moe_assignments_total",
             "arkflow_gen_moe_held_assignments_total")
    before = {(n, k): _counter(n, kind=k).value
              for n in names for k in ("chunk", "decode")}
    hits = {k: server.m_moe[k][1].count for k in ("chunk", "decode")}
    uploads = {k: server.m_uploads[k].value for k in ("chunk", "decode")}
    out = asyncio.run(server.generate(
        np.random.RandomState(1).randint(1, 128, 21).tolist(), 6))
    assert len(out) == 6
    d = {key: _counter(*key[:1], kind=key[1]).value - v for key, v in before.items()}
    assert d[names[0], "chunk"] == 21 * 4 * 4 and d[names[0], "decode"] == 5 * 4 * 4
    assert 0 < d[names[1], "chunk"] < d[names[0], "chunk"]
    assert server.m_moe["chunk"][1].count - hits["chunk"] == 3
    assert server.m_moe["decode"][1].count - hits["decode"] == 5
    # one host array a step, whatever the model counts on the device
    assert server.m_uploads["chunk"].value - uploads["chunk"] == 3
    assert server.m_uploads["decode"].value - uploads["decode"] == 5
    assert [g[1] for g in server.m_kv_live] == ["pages", "window"]
    kv = 2 * CFG.kv_heads * CFG.dh * 2
    assert [g[2] for g in server.m_kv_live] == [PAGE * kv, PAGE * 4 * kv]
    for pool in ("kv", "kv_window"):
        global_registry().gauge("arkflow_gen_kv_live_bytes",
                                labels={"model": "decoder_lm", "pool": pool})
    assert global_registry().gauge(
        "arkflow_gen_kv_bytes_per_token",
        labels={"model": "decoder_lm"}).value == kv_bytes_per_token(CFG)


def test_the_paged_server_passes_its_probe_and_serves():
    proc = _proc(decode_kernel="paged", kernel_interpret=True)
    parity = proc._server.kernel_parity
    assert parity["ok"] and "paged_window_attention_chunk" in parity["kernels"]
    out = asyncio.run(proc._server.generate(PROMPTS[1], 4))
    assert len(out) == 4


# -- what is served and what is still refused ---------------------------------------


@pytest.mark.parametrize("extra,needle", [
    ({"mesh": {"tp": 2}}, "one chip"),
    ({"serving": "batch"}, "serving: continuous"),
    ({"prefill_chunk": 0}, "kv, kv_window.*prefill_chunk > 0"),
    ({"prefix_cache_pages": 8}, "prefix_cache_pages.*kv, kv_window"),
    ({"speculative_tokens": 2}, "speculative_tokens.*kv, kv_window"),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else None)
def test_pattern_model_refuses_what_is_not_served_with_it(extra, needle):
    with pytest.raises(ConfigError, match=needle):
        _proc(**extra)


def test_pattern_model_refuses_kv_push():
    proc = _proc()
    assert getattr(proc, "disagg", None) is None
    with pytest.raises(ConfigError, match="kv, kv_window.*no wire form"):
        asyncio.run(proc._server.prefill_export([1, 2, 3], 2))
    with pytest.raises(ConfigError, match="kv, kv_window.*no wire form"):
        asyncio.run(proc._server.generate_from_pages({"done": False}))


DENSE = dict(vocab_size=64, dim=32, layers=2, heads=4, kv_heads=2, ffn=64)
ROUTED = dict(n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
              moe_intermediate_size=16, first_k_dense_replace=1)


@pytest.mark.parametrize("ok", [
    {"layer_types": (FULL, FULL)},
    {"layer_types": (SLIDING, FULL), "sliding_window": 9},
    {"layer_types": (SLIDING, SLIDING, FULL), "sliding_window": 4},  # a longer list
    {"qk_norm": True, "head_dim": 16},
    {"full_attention_rope": False, "layer_types": (SLIDING, FULL), "sliding_window": 9},
    ROUTED, {**ROUTED, "experts_held": (2, 4)},
    {**ROUTED, "layer_types": (SLIDING, FULL), "sliding_window": 9, "qk_norm": True},
], ids=lambda v: "-".join(v)[:60])
def test_a_per_head_model_is_served_with(ok):
    cfg = dec.DecoderConfig(**{**DENSE, **ok})
    assert cfg.by_runs == bool(cfg.routed or SLIDING in cfg.kinds or cfg.qk_norm)
    out = dec.forward(dec.init(jax.random.PRNGKey(0), cfg), cfg,
                      jnp.arange(12, dtype=jnp.int32).reshape(1, 12))
    assert out.shape == (1, 12, 64) and bool(jnp.isfinite(out).all())


@pytest.mark.parametrize("bad,needle", [
    # what tests/test_sparse_window_moe.py held a dense model to refuse, as
    # cases of what is refused now
    ({"sliding_window": 9}, "go together"),
    ({"layer_types": (SLIDING, FULL)}, "go together"),
    ({"index_topk": 4}, "latent-attention model"),
    ({"attention_gate_type": "headwise"}, "latent-attention model"),
    ({"apply_mla_qkv_lora_rescale": True}, "latent-attention model"),
    ({"layer_types": (SLIDING, FULL), "sliding_window": 9, "swa_heads": 2},
     "latent-attention model"),
    ({"layer_types": (FULL,)}, "names each of the 2 layers"),
    ({"layer_types": (FULL, "linear_attention")}, "a linear_attention layer needs"),
    ({"layer_types": (FULL, "ring_attention")}, "names each of the 2 layers"),
    ({"layer_types": (SLIDING, FULL), "sliding_window": 9, "num_experts": 4},
     "Switch"),
    ({"layer_types": (SLIDING, FULL), "sliding_window": 9,
      "use_ring_attention": True}, "ring attention"),
    ({**ROUTED, "num_experts": 4}, "Switch"),
    ({"qk_norm": True, "num_experts": 4}, "Switch"),
    ({**ROUTED, "first_k_dense_replace": 2}, "leading dense"),
    ({**ROUTED, "scoring_func": "softmax"}, "sigmoid"),
    ({**ROUTED, "experts_held": (6, 4)}, "experts_held"),
    ({"rope_interleave": True}, "rope_interleave"),
    ({"head_dim": 7}, "head_dim"),
], ids=lambda v: "-".join(v)[:60] if isinstance(v, dict) else None)
def test_a_per_head_model_still_refuses(bad, needle):
    with pytest.raises(ConfigError, match=needle):
        dec.DecoderConfig(**{**DENSE, **bad})


def test_a_latent_model_refuses_the_per_head_keys():
    from tests.test_sparse_window_moe import TINY as LATENT

    for bad in ({"qk_norm": True}, {"full_attention_rope": False}):
        with pytest.raises(ConfigError, match="per-head K/V model"):
            dec.DecoderConfig(**{**LATENT, **bad})


def test_the_batch_cache_refuses_a_model_that_stacks_by_runs():
    with pytest.raises(ConfigError, match="serving: continuous"):
        dec.init_kv_cache(CFG, 1, 16)


# -- the judge ----------------------------------------------------------------------


def _teacher_row(params, prompt, new):
    """The reference's own greedy continuation of ``prompt``."""
    row = list(prompt)
    for _ in range(new):
        row.append(int(_reference(params, np.asarray(row, np.int32))[-1].argmax()))
    return row[len(prompt):]


def test_judge_accepts_the_reference_s_own_tokens_and_refuses_others(params):
    prompts = [IDS[:20].tolist(), IDS[20:47].tolist()]
    tokens = [_teacher_row(params, p, 4) for p in prompts]
    hp = ref.hyper(CFG)
    good = ref.judge_rows(params, hp, prompts, tokens, longest=64)
    assert good["ok"] and good["unexplained"] == 0 and good["rerouted"] == 0
    assert good["positions_checked"] == 8
    wrong = [[(t + 1) % 128 for t in toks] for toks in tokens]
    bad = ref.judge_rows(params, hp, prompts, wrong, longest=64)
    assert not bad["ok"] and bad["unexplained"] > 0


def test_judge_refuses_a_window_that_is_a_page_wider(params):
    """The control the builder runs on the chip, at tiny size: tokens served
    with the window one page wider are not the reference's."""
    wide = dataclasses.replace(CFG, sliding_window=CFG.sliding_window + PAGE)
    prompts = [IDS[:40].tolist(), IDS[10:58].tolist()]

    def greedy(cfg, prompt, new=6):
        row = list(prompt)
        with jax.default_matmul_precision("highest"):
            for _ in range(new):
                logits = ref.decoder_logits(
                    params, jnp.asarray(row, jnp.int32), len(row) - 1, new=1,
                    hp=ref.hyper(cfg))[0]
                row.append(int(logits[0].argmax()))
        return row[len(prompt):]

    verdicts = [ref.judge_rows(params, ref.hyper(CFG), prompts,
                               [greedy(cfg, p) for p in prompts], longest=64)
                for cfg in (CFG, wide)]
    assert verdicts[0]["ok"] and verdicts[0]["unexplained"] == 0
    assert not verdicts[1]["ok"] and verdicts[1]["unexplained"] > 0


def test_judge_holds_the_float32_leaves():
    masters = dec.init(jax.random.PRNGKey(3), CFG)
    placed = jax.tree_util.tree_map(lambda leaf, dt: leaf.astype(dt), masters,
                                    dec.serve_dtypes(CFG))
    assert ref.stated_float32_leaves_differ(placed, masters) == 0
    placed["layers"]["q_head_norm"]["scale"] = placed["layers"]["q_head_norm"][
        "scale"].astype(jnp.bfloat16)
    assert ref.stated_float32_leaves_differ(placed, masters) == 4 * 8
