"""Per-head (GQA) layers whose sizes go by kind — full layers of 2 K/V heads
beside sliding-window layers of 4, keys wider than values, partial rotation
at a base a kind, a learned sink in the sliding layers' softmax — with a
held share of sigmoid-routed experts and no shared expert (the MiMo-V2.5
layout), through the paged serving path, held to the plain reference
``benchmark/references/hetero_gqa_moe.py`` on seeded weights at tiny widths:
a window of 9 over pages of 8, so 26- to 58-token rows pass the window, wrap
their ring of window pages and have chunk boundaries inside a window (Pallas
in interpret mode). ``parts`` runs the same at keys of 192 and values of 128:
a key the page pools hold in two parts of 128 lanes.

The equations are held EXACTLY: with the program's products switched to
float32 (``exact``) its logits are the reference's to 2e-4 through the full
forward and through chunked prefill and decode over the cache, choices
included. The bfloat16 program is held kernel by kernel to its plain-XLA
form (the build-time probe) and, served, to the reference's judge.
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
import importlib.util
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
                                             _read_keys, _write_keys, cache_spec,
                                             gqa_kernel_probe, init_page_pool,
                                             kv_bytes_per_token,
                                             paged_decode_step, paged_prefill,
                                             paged_prefill_chunk,
                                             window_ring_pages)
from arkflow_tpu.obs import global_registry
from tests.test_window_gqa_moe import _round_like_placed, _tables

ensure_plugins_loaded()


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/references/hetero_gqa_moe.py", "ref_hetero_gqa_moe")

FULL, SLIDING = dec.FULL, dec.SLIDING
TINY = dict(vocab_size=128, dim=32, layers=7, heads=4, kv_heads=2, swa_kv_heads=4,
            head_dim=24, v_head_dim=16, swa_v_head_dim=16, ffn=64, max_seq=256,
            rope_theta=1e7, swa_rope_theta=1e4, partial_rotary_factor=0.334,
            attention_value_scale=0.707, add_swa_attention_sink_bias=True,
            norm_eps=1e-5, n_routed_experts=16, num_experts_per_tok=4,
            n_shared_experts=0, moe_intermediate_size=16, first_k_dense_replace=1,
            experts_held=(4, 4),
            # the published order's first seven, of a list longer than ``layers``
            layer_types=(FULL, SLIDING, SLIDING, SLIDING, SLIDING, FULL, SLIDING,
                         SLIDING, SLIDING),
            sliding_window=9)
CFG = dec.DecoderConfig(**TINY)
#: keys of 192 in two parts of 128 lanes, values of 128: the published widths
PARTS = dataclasses.replace(CFG, head_dim=192, v_head_dim=128, swa_v_head_dim=128)
CONFIGS = {"tiny": CFG, "parts": PARTS}
PAGE = 8
INTERPRET = dict(attention_kernel="paged", kernel_interpret=True)
KERNELS = pytest.mark.parametrize("kern", [{}, INTERPRET], ids=["gather", "paged"])


def _params(cfg):
    """Seeded weights (the sinks normal(2, 1), as ``init`` seeds them: beside
    a toy window's 9 nearly flat keys such a sink takes half, and leaving it
    out decides tokens); the selection bias at +-0.05, the size of the gaps
    between 16 experts' scores."""
    p = dec.init(jax.random.PRNGKey(3), cfg)
    for name in ("layers", "swa_layers"):
        p[name]["router_bias"] = jax.random.uniform(
            jax.random.PRNGKey(8), p[name]["router_bias"].shape, jnp.float32,
            -0.05, 0.05)
    return _round_like_placed(p, cfg)


@pytest.fixture(scope="module")
def params():
    return _params(CFG)


@pytest.fixture(scope="module")
def all_params():
    return {name: _params(cfg) for name, cfg in CONFIGS.items()}


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


# -- sizes by kind, the cache spec and the layer runs ----------------------------


def test_sizes_go_by_kind_through_one_accessor():
    full, swa = CFG.gqa(FULL), CFG.gqa(SLIDING)
    assert (full.kv_heads, full.dk, full.dv, full.rope_theta, full.rotary,
            full.window, full.sink) == (2, 24, 16, 1e7, 8, 0, False)
    assert (swa.kv_heads, swa.dk, swa.dv, swa.rope_theta, swa.rotary,
            swa.window, swa.sink) == (4, 24, 16, 1e4, 8, 9, True)
    assert CFG.hetero and CFG.kind_stacks and CFG.by_runs and CFG.layered
    # the published widths: 64 of 192 values rotated, a key in two parts
    big = PARTS.gqa(FULL)
    assert (big.rotary, big.key_parts, big.dk_held) == (64, 2, 256)
    assert (full.key_parts, full.dk_held) == (1, 24)  # a narrow head as it is
    assert dec.DecoderConfig(dim=512, heads=2, kv_heads=1, head_dim=256).gqa(
        FULL).key_parts == 1
    # a model of one head size on every layer is none of this
    plain = dec.DecoderConfig(vocab_size=64, dim=32, layers=2, heads=4, kv_heads=2)
    assert not plain.hetero and not plain.kind_stacks and not plain.by_runs


def test_cache_spec_gives_each_pool_its_own_widths():
    pools = {p.name: p for p in cache_spec(CFG)}
    assert list(pools) == ["kv", "kv_window"]
    assert pools["kv"].layers == 2 and pools["kv_window"].layers == 5
    assert pools["kv"].widths == (2 * 24, 2 * 16) and pools["kv"].heads == 2
    assert pools["kv_window"].widths == (4 * 24, 4 * 16)
    assert pools["kv_window"].heads == 4 and pools["kv_window"].window == 9
    # the window row is TWICE the kept row
    assert pools["kv"].bytes_per_token == 2 * 2 * 40 * 2
    assert pools["kv_window"].bytes_per_token == 5 * 4 * 40 * 2
    assert kv_bytes_per_token(CFG) == 2 * 160 + 5 * 320
    kp, vp = init_page_pool(CFG, 7, PAGE, window_pages=5)
    assert kp["kv"].shape == (2, 7, PAGE, 2, 24) and vp["kv"].shape == (2, 7, PAGE, 2, 16)
    assert kp["kv_window"].shape == (5, 5, PAGE, 4, 24)
    assert vp["kv_window"].shape == (5, 5, PAGE, 4, 16)
    # at the published widths a key is HELD in two parts of 128 lanes, a
    # layer of the K array a part: 2 x (256 + 128) x 2 B a token a layer
    big = {p.name: p for p in cache_spec(PARTS)}
    assert big["kv"].widths == (2 * 256, 2 * 128) and big["kv"].key_parts == 2
    assert big["kv"].bytes_per_token == 2 * 2 * 384 * 2
    assert kv_bytes_per_token(PARTS) == 2 * 1536 + 5 * 3072
    kp, vp = init_page_pool(PARTS, 7, PAGE, window_pages=5)
    assert kp["kv"].shape == (2 * 2, 7, PAGE, 2, 128)
    assert vp["kv"].shape == (2, 7, PAGE, 2, 128)
    assert kp["kv_window"].shape == (2 * 5, 5, PAGE, 4, 128)
    # the cell's own: MiMo-V2.5's rows by hand (held and published)
    mimo = dec.DecoderConfig(dim=4096, layers=7, heads=64, kv_heads=4,
                             swa_kv_heads=8, head_dim=192, v_head_dim=128,
                             layer_types=TINY["layer_types"], sliding_window=128)
    rows = {p.name: p.bytes_per_token // p.layers for p in cache_spec(mimo)}
    assert rows == {"kv": 4 * (256 + 128) * 2, "kv_window": 8 * (256 + 128) * 2}
    assert kv_bytes_per_token(mimo) == 2 * 3072 + 5 * 6144
    assert window_ring_pages(mimo, 16, 512) == 41


def test_keys_held_in_parts_are_written_and_read_back_whole():
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 2, 192), jnp.float32)
    kp = jnp.zeros((2 * 3, 5, PAGE, 2, 128), jnp.float32)  # 3 layers x 2 parts
    pi = jnp.asarray([[1, 1, 2], [4, 4, 4]])
    po = jnp.asarray([[6, 7, 0], [0, 1, 2]])
    kp = _write_keys(kp, k, 1, pi, po, 2)
    # part 0 in the pool's layer 1, part 1 (64 values and 64 zeros) in 3 + 1
    np.testing.assert_array_equal(kp[1, 1, 6], k[0, 0, :, :128])
    np.testing.assert_array_equal(kp[4, 1, 6, :, :64], k[0, 0, :, 128:])
    assert not np.asarray(kp[4, :, :, :, 64:]).any() and not np.asarray(kp[0]).any()
    back = _read_keys(kp, 1, jnp.asarray([[1, 2], [4, 0]]), 192)
    assert back.shape == (2, 2 * PAGE, 2, 192)
    np.testing.assert_array_equal(back[0, 6], k[0, 0])
    np.testing.assert_array_equal(back[0, PAGE], k[0, 2])
    np.testing.assert_array_equal(back[1, 2], k[1, 2])


def test_kinds_of_different_shapes_stack_apart():
    assert dec.layer_runs(CFG) == [
        ("dense_layers", 0, 1, FULL, False, 0),
        ("swa_layers", 0, 4, SLIDING, True, 0),
        ("layers", 0, 1, FULL, True, 1),
        ("swa_layers", 4, 5, SLIDING, True, 4)]
    masters = dec.init(jax.random.PRNGKey(3), CFG)
    assert masters["dense_layers"]["wk"]["w"].shape == (1, 32, 2 * 24)
    assert masters["swa_layers"]["wk"]["w"].shape == (5, 32, 4 * 24)
    assert masters["swa_layers"]["wv"]["w"].shape == (5, 32, 4 * 16)
    assert masters["layers"]["wo"]["w"].shape == (1, 4 * 16, 32)
    assert masters["swa_layers"]["attn_sink"].shape == (5, 4)
    assert "attn_sink" not in masters["layers"]
    assert masters["swa_layers"]["experts"]["w_gate"].shape == (5, 4, 32, 16)
    # kinds of ONE shape still share their stacks (K-EXAONE's layout)
    same = dataclasses.replace(CFG, swa_kv_heads=0, add_swa_attention_sink_bias=False)
    assert same.hetero and not same.kind_stacks
    assert [r[0] for r in dec.layer_runs(same)] == ["dense_layers"] + ["layers"] * 3


def test_serve_dtypes_cover_every_leaf_and_state_the_sinks_float32():
    masters = dec.init(jax.random.PRNGKey(3), CFG)
    dtypes = dec.serve_dtypes(CFG)
    assert (jax.tree_util.tree_structure(masters)
            == jax.tree_util.tree_structure(dtypes))
    for path, dt in jax.tree_util.tree_flatten_with_path(dtypes)[0]:
        keys = [str(getattr(k, "key", k)) for k in path]
        stated = any("router" in k or "norm" in k or "sink" in k for k in keys)
        assert (dt == jnp.float32) == stated, keys
    assert jax.tree_util.tree_structure(dec.param_specs(CFG, {})) == \
        jax.tree_util.tree_structure(dtypes)


# -- the equations -----------------------------------------------------------------


@pytest.mark.parametrize("name", list(CONFIGS))
def test_forward_matches_reference(all_params, exact, name):
    cfg, p = CONFIGS[name], all_params[name]
    got = np.asarray(dec.forward(p, cfg, jnp.asarray(IDS)[None]))[0]
    np.testing.assert_allclose(got, _reference(p, IDS, cfg), atol=EXACT)


@pytest.mark.parametrize("ablation", [
    "no_sink", "sink_on_full", "window", "value_scale", "all_rotated",
    "one_base", "kv_heads_swapped_values", "bias"])
def test_reference_comparison_detects(params, exact, ablation):
    """The comparison sees each thing the configuration states or assumes."""
    cfg, p = CFG, params
    if ablation == "no_sink":
        p = {**params, "swa_layers": {**params["swa_layers"], "attn_sink": jnp.full_like(
            params["swa_layers"]["attn_sink"], -1e9)}}
    elif ablation == "sink_on_full":
        cfg = dataclasses.replace(CFG, add_full_attention_sink_bias=True)
        p = {**params, **{name: {**params[name], "attn_sink": jnp.ones((1, 4))}
                          for name in ("dense_layers", "layers")}}
    elif ablation == "window":
        cfg = dataclasses.replace(CFG, sliding_window=10)
    elif ablation == "value_scale":
        cfg = dataclasses.replace(CFG, attention_value_scale=1.0)
    elif ablation == "all_rotated":
        cfg = dataclasses.replace(CFG, partial_rotary_factor=1.0)
    elif ablation == "one_base":
        cfg = dataclasses.replace(CFG, swa_rope_theta=0.0)
    elif ablation == "kv_heads_swapped_values":
        p = {**params, "swa_layers": {**params["swa_layers"], "wv": {
            "w": params["swa_layers"]["wv"]["w"].reshape(5, 32, 4, 16)[:, :, ::-1]
            .reshape(5, 32, 64)}}}
    else:
        p = {**params, "swa_layers": {**params["swa_layers"], "router_bias": jnp.zeros_like(
            params["swa_layers"]["router_bias"])}}
    got = np.asarray(dec.forward(p, cfg, jnp.asarray(IDS)[None]))[0]
    assert np.abs(got - _reference(params, IDS)).max() > 50 * EXACT


def test_the_sink_by_hand_on_three_keys():
    """One head, three keys with scores 0, ln 2, ln 3 and a sink of ln 4:
    the probabilities are 1, 2, 3 over 1 + 2 + 3 + 4, and the sink's tenth
    of four adds no value."""
    q = jnp.asarray([[[[1.0]]]])                                  # [B, S, H, d]
    k = jnp.log(jnp.asarray([1.0, 2.0, 3.0])).reshape(1, 3, 1, 1)
    v = jnp.asarray([10.0, 20.0, 30.0]).reshape(1, 3, 1, 1)
    plain = cm.attention(q, k, v, None)
    np.testing.assert_allclose(np.asarray(plain)[0, 0, 0], [140 / 6], rtol=1e-6)
    sunk = cm.attention(q, k, v, None, sink=jnp.log(jnp.asarray([4.0])))
    np.testing.assert_allclose(np.asarray(sunk)[0, 0, 0], [140 / 10], rtol=1e-6)
    # a sink of 0 is one more key of score 0: it still takes a seventh
    zero = cm.attention(q, k, v, None, sink=jnp.zeros((1,)))
    np.testing.assert_allclose(np.asarray(zero)[0, 0, 0], [140 / 7], rtol=1e-6)


def test_partial_rotation_leaves_the_rest_bit_equal_at_a_base_a_kind(params):
    lp = jax.tree_util.tree_map(lambda a: a[0], {
        k: v for k, v in params["swa_layers"].items() if k != "experts"})
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 4, 24), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 5, 4, 24), jnp.float32)
    pos = jnp.asarray([[3, 4, 5, 6, 700]])
    rotated = {}
    for kind in (FULL, SLIDING):
        kk = k[:, :, :CFG.gqa(kind).kv_heads]
        rq, rk = dec.qk_positioned(lp, q, kk, CFG, pos, kind)
        np.testing.assert_array_equal(np.asarray(rq[..., 8:]), np.asarray(q[..., 8:]))
        np.testing.assert_array_equal(np.asarray(rk[..., 8:]), np.asarray(kk[..., 8:]))
        # the first 8 values by hand: split halves (i, i + 4) at the kind's base
        theta = {FULL: 1e7, SLIDING: 1e4}[kind]
        ang = np.asarray(pos, np.float64)[0, :, None] * theta ** (-np.arange(4) / 4)
        a, b = np.asarray(q, np.float64)[0, :, :, :4], np.asarray(q, np.float64)[0, :, :, 4:8]
        want = np.concatenate([a * np.cos(ang)[:, None] - b * np.sin(ang)[:, None],
                               a * np.sin(ang)[:, None] + b * np.cos(ang)[:, None]], -1)
        np.testing.assert_allclose(np.asarray(rq)[0, ..., :8], want, atol=2e-5)
        rotated[kind] = np.asarray(rq)
    assert np.abs(rotated[FULL] - rotated[SLIDING]).max() > 0.1


def _through_the_cache(cfg, params, rows, lens, new, chunk, kern, pages_per=8):
    """Chunked prefill of three ragged rows, then lockstep decode steps fed
    the rows' own tokens: every step's logits, a row at a time, and the
    counters of each chunk and decode step. Pools float32 (``exact``)."""
    (kept, ring), cols = _tables(cfg, 3, pages_per, chunk)
    kp, vp = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        init_page_pool(cfg, 1 + 3 * pages_per, PAGE, 1 + 3 * cols))
    chunked = jax.jit(lambda p, *a: paged_prefill_chunk(p, cfg, *a, **kern))
    step = jax.jit(lambda p, *a: paged_decode_step(
        p, cfg, *a, return_logits=True, **kern))
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
@pytest.mark.parametrize("name,chunk", [("tiny", 8), ("tiny", 12), ("parts", 12)])
def test_chunked_prefill_then_decode_matches_reference(all_params, exact, name,
                                                       chunk, kern):
    """Rows of 41, 26 and 53 tokens pass the window (9) and wrap their ring
    (3 or 4 pages of 8); chunks of 8 and of 12 put chunk boundaries inside a
    window and off the page grid. The logits of every step are the
    reference's full-forward logits and the counters a hand count, through
    plain XLA and through the Pallas kernels (the sink and the window's
    lower bound over the ring, K/V heads and widths by kind, a key in
    parts, the expert product)."""
    cfg, p = CONFIGS[name], all_params[name]
    got, counts = _through_the_cache(cfg, p, ROWS, LENS, NEW, chunk, kern)
    for r, n in enumerate(LENS):
        want = _reference(p, ROWS[r][:n + NEW - 1], cfg)
        np.testing.assert_allclose(got[r], want[n - 1:], atol=EXACT)
    for n, (pairs, hit, load, here) in counts:
        assert pairs == n * 4 * 6 and 0 <= here <= pairs
        assert 0 < hit <= 4 * 6 and 0 < load <= n


@KERNELS
def test_a_sink_on_full_layers_is_served_by_the_same_code(exact, kern):
    cfg = dataclasses.replace(CFG, add_full_attention_sink_bias=True)
    p = _params(cfg)
    assert p["layers"]["attn_sink"].shape == (1, 4)
    assert float(p["layers"]["attn_sink"].mean()) > 1.0
    got, _ = _through_the_cache(cfg, p, ROWS, LENS, NEW, 12, kern)
    for r, n in enumerate(LENS):
        want = _reference(p, ROWS[r][:n + NEW - 1], cfg)
        np.testing.assert_allclose(got[r], want[n - 1:], atol=EXACT)


def test_one_shot_prefill_refuses_a_layer_pattern(params):
    (kept, _), _ = _tables(CFG, 1, 8, 8)
    kp, vp = init_page_pool(CFG, 9, PAGE, 4)
    with pytest.raises(ConfigError, match="kv, kv_window.*prefills in chunks"):
        paged_prefill(params, CFG, jnp.zeros((1, 16), jnp.int32),
                      jnp.asarray([9]), kept, kp, vp)


# -- the kernel against its plain-XLA twin -----------------------------------------


@pytest.mark.parametrize("name", list(CONFIGS))
def test_kernel_probe_covers_both_kinds_kernels(all_params, name):
    from arkflow_tpu.tpu.serving_core import logits_parity

    out = gqa_kernel_probe(all_params[name], CONFIGS[name], PAGE, kernel_interpret=True)
    assert [n for n, _, _ in out] == [
        "paged_attention_decode", "paged_attention_chunk",
        "paged_window_attention_decode", "paged_window_attention_chunk",
        "expert_product"]
    for n, want, got in out:
        assert want.shape == got.shape and logits_parity(want, got)["ok"], n


@pytest.mark.parametrize("sink", [False, True], ids=["no_sink", "sink"])
@pytest.mark.parametrize("dk,dv,kvh,window,page,c,offs", [
    (24, 16, 4, 9, 8, 1, (0, 7, 30, 101)), (24, 16, 4, 9, 8, 12, (0, 5, 40, 99)),
    (24, 16, 2, 16, 8, 24, (0, 16, 33, 64)),
    (192, 128, 4, 128, 16, 1, (1, 127, 128, 4000)),
    (192, 128, 2, 128, 16, 40, (0, 100, 300, 1000)),
    (192, 128, 1, 40, 4, 8, (0, 36, 95, 642, 3001))])
def test_windowed_kernel_with_and_without_the_sink_matches_attend_ring(
        sink, dk, dv, kvh, window, page, c, offs):
    """Rows at their start, inside their first window, past it and past the
    ring's wrap, each ring holding only the pages a server would hold; keys
    wider than values, a key in parts, 1 to 4 query heads a K/V head."""
    cfg = dataclasses.replace(CFG, sliding_window=window, head_dim=dk,
                              v_head_dim=dv, swa_v_head_dim=dv, swa_kv_heads=kvh)
    sp = cfg.gqa(SLIDING)
    cols = window_ring_pages(cfg, page, c)
    b = len(offs)
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 5))
    rand = lambda shape: jax.random.normal(next(keys), shape, jnp.float32)  # noqa: E731
    kp = rand((2 * sp.key_parts, 1 + b * cols, page, kvh, sp.dk_held // sp.key_parts))
    vp = rand((2, 1 + b * cols, page, kvh, dv))
    ring = np.zeros((b, cols), np.int32)
    for r, off in enumerate(offs):
        oldest = max(off - (window - 1), 0) // page
        for i in range(oldest, (off + c - 1) // page + 1):
            ring[r, i % cols] = 1 + r * cols + i % cols
    ring, off = jnp.asarray(ring), jnp.asarray(offs, jnp.int32)
    q = rand((b, c, 4, dk))
    logits = rand((4,)) if sink else None
    positions = off[:, None] + jnp.arange(c)[None, :]
    want = _attend_ring(q, kp, vp, 1, ring, positions, window, logits)
    got = _attend_paged(q, kp, vp, 1, ring, off, cfg, None, True, window, logits)
    assert got.shape == (b, c, 4, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)
    if sink:  # and the sink moved it
        assert np.abs(np.asarray(want) - np.asarray(_attend_ring(
            q, kp, vp, 1, ring, positions, window))).max() > 1e-2


def test_on_a_chip_a_sink_needs_the_kernel_s_own_walk():
    """Compiled (not interpreted) pools [.., kv heads, width] are walked at
    heads of multiples of 128 lanes; a narrower head is served from
    row-major pools, keys and values of one width and no sink: keys of 24
    beside values of 16 are refused by name."""
    from arkflow_tpu.ops.ragged_attention import paged_flash_attention

    q = jnp.zeros((1, 1, 4, 24), jnp.bfloat16)
    kp = jnp.zeros((1, 3, PAGE, 2, 24), jnp.bfloat16)
    vp = jnp.zeros((1, 3, PAGE, 2, 16), jnp.bfloat16)
    with pytest.raises(ValueError, match="row-major: cache_spec.*keys 24, values 16"):
        paged_flash_attention(q, kp, vp, 0, jnp.zeros((1, 2), jnp.int32),
                              jnp.zeros((1,), jnp.int32))


# -- the held share ---------------------------------------------------------------


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen chips hold 1 of the 16 experts each: their routed parts are
    the uncut layer's output (no shared expert), by the program and by the
    reference alike."""
    uncut_cfg = dataclasses.replace(CFG, experts_held=None)
    whole = _round_like_placed(dec.init(jax.random.PRNGKey(3), uncut_cfg), uncut_cfg)
    lp = jax.tree_util.tree_map(lambda a: a[0], whole["swa_layers"])
    y = jax.random.normal(jax.random.PRNGKey(4), (1, 24, CFG.dim), jnp.float32)
    hp = ref.hyper(uncut_cfg)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.routed_experts(lp, y[0], hp)[0])
        np.testing.assert_allclose(
            np.asarray(dec.routed_mlp(lp, y, uncut_cfg)[0])[0], want, atol=5e-5)
        total, loads = np.zeros_like(want), []
        for first in range(16):
            share = dataclasses.replace(CFG, experts_held=(first, 1))
            ex = {k: v[first:first + 1] for k, v in lp["experts"].items()}
            out, load = dec.routed_mlp({**lp, "experts": ex}, y, share)
            total += np.asarray(out, np.float32)[0]
            loads.append(np.asarray(load))
            part = np.asarray(ref.routed_experts(
                {**lp, "experts": ex}, y[0], {**hp, "held": (first, 1)})[0])
            np.testing.assert_allclose(np.asarray(out)[0], part, atol=2e-5)
    np.testing.assert_allclose(total, want, atol=5e-5)
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


def test_server_counters_and_gauges_equal_a_hand_count():
    """One prompt of 21 tokens (chunks of 8: 8 + 8 + 5) and 6 new tokens."""
    proc = _proc()
    server = proc._server
    names = ("arkflow_gen_moe_assignments_total",
             "arkflow_gen_moe_held_assignments_total",
             "arkflow_gen_attn_sink_rows_total")
    before = {(n, k): _counter(n, kind=k).value
              for n in names for k in ("chunk", "decode")}
    hits = {k: server.m_moe[k][1].count for k in ("chunk", "decode")}
    out = asyncio.run(server.generate(
        np.random.RandomState(1).randint(1, 128, 21).tolist(), 6))
    assert len(out) == 6
    d = {key: _counter(*key[:1], kind=key[1]).value - v for key, v in before.items()}
    # 6 expert layers, 4 choices a token
    assert d[names[0], "chunk"] == 21 * 4 * 6 and d[names[0], "decode"] == 5 * 4 * 6
    assert 0 < d[names[1], "chunk"] < d[names[0], "chunk"]
    assert server.m_moe["chunk"][1].count - hits["chunk"] == 3
    assert server.m_moe["decode"][1].count - hits["decode"] == 5
    # the five sliding layers have a sink: a row a query a layer, no padding
    assert d[names[2], "chunk"] == 21 * 5 and d[names[2], "decode"] == 5 * 5
    # the gauges read the spec: a page of kept rows, a page of window rows
    assert [g[1] for g in server.m_kv_live] == ["pages", "window"]
    assert [g[2] for g in server.m_kv_live] == [PAGE * 2 * 2 * 40 * 2,
                                                PAGE * 5 * 4 * 40 * 2]
    assert global_registry().gauge(
        "arkflow_gen_kv_bytes_per_token",
        labels={"model": "decoder_lm"}).value == kv_bytes_per_token(CFG) == 1920


def test_a_model_without_a_sink_has_no_sink_counter():
    proc = _proc({"add_swa_attention_sink_bias": False})
    assert proc._server.m_sink_rows == {} and proc._server._sink_layers == 0


@pytest.mark.parametrize("name", list(CONFIGS))
def test_the_paged_server_passes_its_probe_and_serves(name):
    cfg = CONFIGS[name]
    proc = _proc({"head_dim": cfg.head_dim, "v_head_dim": cfg.v_head_dim,
                  "swa_v_head_dim": cfg.swa_v_head_dim},
                 decode_kernel="paged", kernel_interpret=True)
    parity = proc._server.kernel_parity
    assert parity["ok"] and "paged_window_attention_chunk" in parity["kernels"]
    out = asyncio.run(proc._server.generate(PROMPTS[1], 4))
    assert len(out) == 4
    walked = _counter("arkflow_gen_attn_pages_walked_total", kind="decode").value
    assert walked > 0


# -- what is served and what is still refused ---------------------------------------


@pytest.mark.parametrize("extra,needle", [
    ({"mesh": {"tp": 2}}, "one chip"),
    ({"serving": "batch"}, "serving: continuous"),
    ({"prefill_chunk": 0}, "kv, kv_window.*prefill_chunk > 0"),
    ({"prefix_cache_pages": 8}, "prefix_cache_pages.*kv, kv_window"),
    ({"speculative_tokens": 2}, "speculative_tokens.*kv, kv_window"),
    ({"swap": {"watch": "/nowhere"}}, "swap is not supported.*head sizes by kind"),
    ({"integrity": {"interval": "1s"}}, "integrity is not supported"),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else None)
def test_the_model_refuses_what_is_not_served_with_it(extra, needle):
    with pytest.raises(ConfigError, match=needle):
        _proc(**extra)


def test_the_model_refuses_kv_push_by_its_pools():
    proc = _proc()
    assert getattr(proc, "disagg", None) is None
    with pytest.raises(ConfigError, match="kv, kv_window.*no wire form"):
        asyncio.run(proc._server.prefill_export([1, 2, 3], 2))
    with pytest.raises(ConfigError, match="kv, kv_window.*no wire form"):
        asyncio.run(proc._server.generate_from_pages({"done": False}))
    # K and V of different widths alone (no pattern) have no wire form either
    plain = build_component("processor", {
        "type": "tpu_generate", "model": "decoder_lm",
        "model_config": {**DENSE, "head_dim": 16, "v_head_dim": 8},
        "serving": "continuous", "max_input": 32, "max_new_tokens": 2,
        "slots": 2, "page_size": PAGE, "seq_buckets": [16], "eos_id": -1,
        "decode_kernel": "gather", "seed": 1}, Resource())
    assert getattr(plain, "disagg", None) is None
    with pytest.raises(ConfigError, match="different widths"):
        asyncio.run(plain._server.prefill_export([1, 2, 3], 2))
    assert len(asyncio.run(plain._server.generate([1, 2, 3, 4], 2))) == 2


DENSE = dict(vocab_size=64, dim=32, layers=2, heads=4, kv_heads=2, ffn=64)
PATTERN = {"layer_types": (SLIDING, FULL), "sliding_window": 9}


@pytest.mark.parametrize("ok", [
    {"head_dim": 16, "v_head_dim": 8},
    {"partial_rotary_factor": 0.5},
    {"attention_value_scale": 0.5},
    {"add_full_attention_sink_bias": True},
    {**PATTERN, "swa_kv_heads": 4},
    {**PATTERN, "swa_rope_theta": 1e4},
    {**PATTERN, "head_dim": 16, "v_head_dim": 16, "swa_v_head_dim": 8},
    {**PATTERN, "add_swa_attention_sink_bias": True, "add_full_attention_sink_bias": True},
    {**PATTERN, "swa_kv_heads": 1, "qk_norm": True, "full_attention_rope": False},
], ids=lambda v: "-".join(v)[:60])
def test_a_per_head_model_is_served_with(ok):
    cfg = dec.DecoderConfig(**{**DENSE, **ok})
    assert cfg.by_runs
    out = dec.forward(dec.init(jax.random.PRNGKey(0), cfg), cfg,
                      jnp.arange(12, dtype=jnp.int32).reshape(1, 12))
    assert out.shape == (1, 12, 64) and bool(jnp.isfinite(out).all())


@pytest.mark.parametrize("bad,needle", [
    ({"swa_kv_heads": 4}, "swa_kv_heads.*without a sliding_attention"),
    ({"add_swa_attention_sink_bias": True}, "without a sliding_attention"),
    ({"swa_rope_theta": 1e4}, "without a sliding_attention"),
    ({**PATTERN, "swa_kv_heads": 3}, "swa_kv_heads 3.*divide the 4 query heads"),
    ({"kv_heads": 3}, "kv_heads 3.*divide the 4 query heads"),
    ({"head_dim": 24, "partial_rotary_factor": 0.3}, "partial_rotary_factor"),
    ({"partial_rotary_factor": 0.0}, "partial_rotary_factor"),
    ({"partial_rotary_factor": 1.5}, "partial_rotary_factor"),
    ({"attention_value_scale": 0.0}, "attention_value_scale"),
    ({"v_head_dim": -8}, "v_head_dim"),
    ({**PATTERN, "swa_heads": 2}, "latent-attention model"),
    ({**PATTERN, "swa_qk_rope_head_dim": 4}, "latent-attention model"),
    ({**PATTERN, "swa_q_lora_rank": 8}, "latent-attention model"),
    ({"v_head_dim": 4, "num_experts": 4}, "Switch"),
    ({"add_full_attention_sink_bias": True, "use_ring_attention": True},
     "ring attention"),
    ({"attention_value_scale": 0.5, "mamba_d_ssm": 32, "mamba_n_heads": 4,
      "mamba_d_head": 8, "mamba_d_state": 8}, "hybrid block"),
], ids=lambda v: "-".join(v)[:60] if isinstance(v, dict) else None)
def test_a_per_head_model_refuses_by_name(bad, needle):
    with pytest.raises(ConfigError, match=needle):
        dec.DecoderConfig(**{**DENSE, **bad})


def test_a_latent_model_refuses_the_per_head_kinds_keys():
    from tests.test_sparse_window_moe import TINY as LATENT

    for bad in ({"swa_kv_heads": 2}, {"partial_rotary_factor": 0.5},
                {"attention_value_scale": 0.707},
                {"add_swa_attention_sink_bias": True},
                {"add_full_attention_sink_bias": True}):
        with pytest.raises(ConfigError, match="per-head K/V model"):
            dec.DecoderConfig(**{**LATENT, **bad})


def test_the_batch_cache_refuses_sizes_by_kind():
    for cfg in (CFG, dec.DecoderConfig(**{**DENSE, "head_dim": 16, "v_head_dim": 8})):
        with pytest.raises(ConfigError, match="head sizes by kind.*serving: continuous"):
            dec.init_kv_cache(cfg, 1, 16)


# -- the judge ----------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _next_token(cfg):
    hp = ref.hyper(cfg)
    return jax.jit(lambda p, row, at: ref.decoder_logits(
        p, row, at, new=1, hp=hp)[0][0].argmax())


def _greedy(params, cfg, prompt, new, width=96):
    """The reference's own greedy continuation of ``prompt`` under ``cfg``
    (one jitted forward a token over a padded row: causal attention never
    looks at the padding)."""
    row, n = np.zeros((width,), np.int32), len(prompt)
    row[:n] = prompt
    with jax.default_matmul_precision("highest"):
        for _ in range(new):
            row[n] = int(_next_token(cfg)(params, jnp.asarray(row), n - 1))
            n += 1
    return row[len(prompt):n].tolist()


JUDGED = [IDS[:40].tolist(), IDS[10:58].tolist(), IDS[5:35].tolist()]


def test_judge_accepts_the_reference_s_own_tokens_and_refuses_others(params):
    tokens = [_greedy(params, CFG, p, 4) for p in JUDGED[:2]]
    hp = ref.hyper(CFG)
    good = ref.judge_rows(params, hp, JUDGED[:2], tokens, longest=96)
    assert good["ok"] and good["unexplained"] == 0 and good["rerouted"] == 0
    assert good["positions_checked"] == 8
    wrong = [[(t + 1) % 128 for t in toks] for toks in tokens]
    bad = ref.judge_rows(params, hp, JUDGED[:2], wrong, longest=96)
    assert not bad["ok"] and bad["unexplained"] > 0


def test_judge_refuses_tokens_served_with_the_sink_left_out(params):
    """The control the builder runs on the chip, at tiny size: tokens served
    by a model whose sliding layers' softmax has no sink are not the
    reference's."""
    sinkless = dataclasses.replace(CFG, add_swa_attention_sink_bias=False)
    verdicts = [ref.judge_rows(params, ref.hyper(CFG), JUDGED,
                               [_greedy(params, cfg, p, 8) for p in JUDGED],
                               longest=96)
                for cfg in (CFG, sinkless)]
    assert verdicts[0]["ok"] and verdicts[0]["unexplained"] == 0
    assert not verdicts[1]["ok"]
    assert verdicts[1]["unexplained_share"] > 5 * ref.UNEXPLAINED_SHARE


def test_judge_refuses_products_at_three_mantissa_bits(params):
    """The other control: the weights of every product rounded to e4m3's 3
    mantissa bits."""
    def coarse(leaf):
        if leaf.ndim < 2:
            return leaf
        m, e = jnp.frexp(leaf)
        return jnp.ldexp(jnp.round(m * 16) / 16, e)

    rough = jax.tree_util.tree_map(coarse, params)
    tokens = [_greedy(rough, CFG, p, 8) for p in JUDGED]
    verdict = ref.judge_rows(params, ref.hyper(CFG), JUDGED, tokens, longest=96)
    assert not verdict["ok"]
    assert verdict["unexplained_share"] > 2 * ref.UNEXPLAINED_SHARE


def test_judge_holds_the_float32_leaves_and_the_sinks():
    masters = dec.init(jax.random.PRNGKey(3), CFG)
    placed = jax.tree_util.tree_map(lambda leaf, dt: leaf.astype(dt), masters,
                                    dec.serve_dtypes(CFG))
    assert ref.stated_float32_leaves_differ(placed, masters) == 0
    assert ref.sinks_differ(placed, masters) == 0
    placed["swa_layers"]["attn_sink"] = placed["swa_layers"]["attn_sink"].astype(
        jnp.bfloat16)
    assert ref.sinks_differ(placed, masters) == 5 * 4
