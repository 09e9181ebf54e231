"""Gated short-convolution layers among grouped-query attention layers of
narrow heads, two leading dense SwiGLUs and then sigmoid-routed experts held
whole (the LFM2-8B-A1B layout), through the paged serving path, held to the
plain reference ``benchmark/references/conv_gqa_moe.py`` on seeded weights at
tiny widths: seven layers C C A C C C A, heads of 64 (two K/V heads: one
128-lane run of a row-major pool) and, ``h32``, of 32 (four K/V heads), pages
of 8, conv windows of two rows a slot (Pallas in interpret mode).

The equations are held EXACTLY: with the program's products switched to
float32 (``exact``) its logits are the reference's to 2e-4 through the full
forward and through chunked prefill and decode over pages and conv rows,
choices included. The bfloat16 program is held kernel by kernel to its
plain-XLA form (the build-time probe) and, served, to the reference's judge,
which refuses each control the builder runs on the chip (``CONTROLS``).
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
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
from arkflow_tpu.models import paged_decode as pd
from arkflow_tpu.models.paged_decode import (cache_spec, gqa_kernel_probe,
                                             init_page_pool, kv_bytes_per_token,
                                             paged_decode_step, paged_prefill,
                                             paged_prefill_chunk)
from arkflow_tpu.obs import global_registry

ensure_plugins_loaded()


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/references/conv_gqa_moe.py", "ref_conv_gqa_moe")

FULL, CONV = dec.FULL, dec.CONV
#: the published order's first seven, of a list longer than ``layers``
KINDS = (CONV, CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV)
TINY = dict(vocab_size=128, dim=32, layers=7, heads=4, kv_heads=2, head_dim=64,
            ffn=64, max_seq=256, rope_theta=1e6, norm_eps=1e-5, qk_norm=True,
            layer_types=KINDS, conv_L_cache=3, n_routed_experts=8,
            num_experts_per_tok=2, n_shared_experts=0, moe_intermediate_size=16,
            first_k_dense_replace=2, norm_topk_eps=1e-6,
            router_bias_std=0.1)
CFG = dec.DecoderConfig(**TINY)
#: heads of 32, four K/V heads: the other width a 128-lane run holds whole
H32 = dataclasses.replace(CFG, head_dim=32, kv_heads=4)
CONFIGS = {"h64": CFG, "h32": H32}
PAGE = 8
INTERPRET = dict(attention_kernel="paged", kernel_interpret=True)
KERNELS = pytest.mark.parametrize("kern", [{}, INTERPRET], ids=["gather", "paged"])


def _params(cfg):
    """Seeded weights as placed (the selection bias normal(0, 0.1), as the
    cell's: ``router_bias_std``), head-norm scales off 1."""
    p = dec.init(jax.random.PRNGKey(3), cfg)
    for i, norm in enumerate(("q_head_norm", "k_head_norm")):
        p["layers"][norm]["scale"] = jax.random.uniform(
            jax.random.PRNGKey(11 + i), p["layers"][norm]["scale"].shape,
            jnp.float32, 0.5, 1.5)
    return jax.tree_util.tree_map(
        lambda leaf, dt: leaf.astype(dt).astype(jnp.float32), p,
        dec.serve_dtypes(cfg))


@pytest.fixture(scope="module")
def all_params():
    return {name: _params(cfg) for name, cfg in CONFIGS.items()}


@pytest.fixture(scope="module")
def params(all_params):
    return all_params["h64"]


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


# -- the third kind: config, cache spec, layer runs ---------------------------------


def test_conv_is_a_kind_of_layer_with_no_attention_weights():
    assert CFG.conv and CFG.stateful and CFG.by_runs and CFG.kind_stacks
    assert not CFG.hybrid and not CFG.layered and not CFG.hetero
    assert CFG.first_k_dense_replace == 2 and CFG.dense_layers == 2
    assert CFG.attn_kinds == (FULL, FULL) and CFG.kinds == KINDS[:7]
    assert dec.layer_runs(CFG) == [
        ("conv_dense_layers", 0, 2, CONV, False, 0),
        ("layers", 0, 1, FULL, True, 0),
        ("conv_layers", 0, 3, CONV, True, 2),
        ("layers", 1, 2, FULL, True, 1)]
    masters = dec.init(jax.random.PRNGKey(3), CFG)
    assert set(masters) == {"embed", "norm_out", "lm_head", "conv_dense_layers",
                            "conv_layers", "layers"}
    conv = masters["conv_layers"]
    assert conv["conv_in"]["w"].shape == (3, 32, 96)        # B | C | u
    assert conv["conv_w"].shape == (3, 32, 3) and conv["conv_out"]["w"].shape == (3, 32, 32)
    assert not {"wq", "wk", "wv", "wo"} & set(conv) and "conv_b" not in conv
    assert conv["experts"]["w_gate"].shape == (3, 8, 32, 16)
    assert masters["conv_dense_layers"]["w_gate"]["w"].shape == (2, 32, 64)
    assert masters["layers"]["wk"]["w"].shape == (2, 32, 2 * 64)
    assert masters["lm_head"]["w"].shape == masters["embed"]["table"].shape[::-1]


def test_serve_dtypes_cover_every_leaf_the_conv_s_three_bfloat16():
    masters = dec.init(jax.random.PRNGKey(3), CFG)
    dtypes = dec.serve_dtypes(CFG)
    assert (jax.tree_util.tree_structure(masters)
            == jax.tree_util.tree_structure(dtypes))
    for path, dt in jax.tree_util.tree_flatten_with_path(dtypes)[0]:
        keys = [str(getattr(k, "key", k)) for k in path]
        stated = any("router" in k or "norm" in k for k in keys)
        assert (dt == jnp.float32) == stated, keys
    assert jax.tree_util.tree_structure(dec.param_specs(CFG, {})) == \
        jax.tree_util.tree_structure(dtypes)


def test_cache_spec_states_kv_over_attention_layers_and_a_conv_pool_a_slot():
    kv, conv = cache_spec(CFG)
    assert (kv.name, kv.layers, kv.heads, kv.row_major) == ("kv", 2, 2, True)
    assert kv.widths == (2 * 64, 2 * 64) and kv.bytes_per_slot == 0
    assert (conv.name, conv.layers, conv.per_slot) == ("conv", 5, True)
    assert conv.bytes_per_token == 0 and conv.bytes_per_slot == 5 * 2 * 32 * 2
    assert kv_bytes_per_token(CFG) == kv.bytes_per_token == 2 * 2 * 128 * 2
    kp, vp = init_page_pool(CFG, 9, PAGE, slots=3)
    assert set(kp) == {"kv", "conv"} and set(vp) == {"kv"}
    assert kp["kv"].shape == vp["kv"].shape == (2, 9, PAGE, 2 * 64)
    assert kp["conv"].shape == (5, 4, 2, 32) and kp["conv"].dtype == jnp.bfloat16
    # the cell's own: LFM2's cut by hand (ISSUE 46: 6,144 B a token, 73,728 B a slot)
    lfm2 = dec.DecoderConfig(
        vocab_size=65536, dim=2048, layers=12, heads=32, kv_heads=8, ffn=7168,
        layer_types=(CONV, CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV, CONV,
                     FULL, CONV), conv_L_cache=3, qk_norm=True,
        n_routed_experts=32, num_experts_per_tok=4, moe_intermediate_size=1792,
        first_k_dense_replace=2)
    kv, conv = cache_spec(lfm2)
    assert kv.shapes(1000, 16) == [(3, 1000, 16, 512)] * 2 and kv.row_major
    assert kv_bytes_per_token(lfm2) == 6144 and conv.bytes_per_slot == 73728
    assert lfm2.dh == 64 and lfm2.gqa(FULL).row_major
    # a head of 128 lanes keeps the layout it had
    wide = dataclasses.replace(CFG, head_dim=128)
    assert cache_spec(wide)[0].shapes(9, PAGE) == [(2, 9, PAGE, 2, 128)] * 2


@pytest.mark.parametrize("bad,needle", [
    ({"conv_L_cache": 0}, "conv_L_cache >= 2"),
    ({"layer_types": (FULL,) * 7}, "conv_L_cache / conv_bias without a conv layer"),
    ({"layer_types": (CONV,) * 7}, "among full_attention"),
    ({"layer_types": (CONV, dec.SLIDING, FULL) * 3, "sliding_window": 8},
     "sliding window's pool"),
    ({"mamba_d_ssm": 32, "mamba_n_heads": 4, "mamba_d_head": 8,
      "mamba_d_state": 8}, "hybrid block"),
    ({"conv_bias": True}, "a bias a channel on the conv is not served"),
    ({"layer_types": ("conv", "convolution") * 4}, "'conv'"),
], ids=lambda v: "-".join(v)[:40] if isinstance(v, dict) else None)
def test_the_conv_kind_refuses_by_name(bad, needle):
    with pytest.raises(ConfigError, match=needle):
        dec.DecoderConfig(**{**TINY, **bad})


def test_a_conv_model_without_experts_is_served_too():
    cfg = dec.DecoderConfig(vocab_size=64, dim=32, layers=3, heads=4, kv_heads=2,
                            ffn=64, layer_types=(CONV, FULL, CONV), conv_L_cache=4)
    assert cfg.by_runs and [r[0] for r in dec.layer_runs(cfg)] == [
        "conv_layers", "layers", "conv_layers"]
    out = dec.forward(dec.init(jax.random.PRNGKey(0), cfg), cfg,
                      jnp.arange(12, dtype=jnp.int32).reshape(1, 12))
    assert out.shape == (1, 12, 64) and bool(jnp.isfinite(out).all())


# -- the equations -----------------------------------------------------------------


@pytest.mark.parametrize("name", list(CONFIGS))
def test_forward_matches_reference(all_params, exact, name):
    cfg, p = CONFIGS[name], all_params[name]
    got = np.asarray(dec.forward(p, cfg, jnp.asarray(IDS)[None]))[0]
    np.testing.assert_allclose(got, _reference(p, IDS, cfg), atol=EXACT)


def test_the_conv_by_hand_on_four_tokens():
    """One channel, taps (1, 10, 100), gated inputs 1, 2, 3, 4: output t
    reads v_{t-2}, v_{t-1}, v_t, zeros before the sequence; a window of the
    last two inputs carries a second block on."""
    cfg = dec.DecoderConfig(vocab_size=8, dim=1, layers=2, heads=1, kv_heads=1,
                            head_dim=2, layer_types=(CONV, FULL), conv_L_cache=3)
    lp = {"conv_in": {"w": jnp.asarray([[1.0, 1.0, 1.0]])},   # B = C = u = y
          "conv_w": jnp.asarray([[1.0, 10.0, 100.0]]),
          "conv_out": {"w": jnp.asarray([[1.0]])}}
    y = jnp.asarray([1.0, np.sqrt(2.0), np.sqrt(3.0), 2.0], jnp.float32).reshape(1, 4, 1)
    with jax.default_matmul_precision("highest"):
        out, ext = dec.short_conv(lp, y.astype(jnp.float32), cfg)
    v = np.asarray(ext, np.float32)[0, :, 0]
    np.testing.assert_allclose(v, [0, 0, 1, 2, 3, 4], rtol=1e-2)
    want = np.asarray([100 * 1, 10 * 1 + 100 * 2, 1 + 20 + 300, 2 + 30 + 400]
                      ) * np.asarray(y)[0, :, 0]
    np.testing.assert_allclose(np.asarray(out, np.float32)[0, :, 0], want, rtol=2e-2)
    tail, _ = dec.short_conv(lp, y[:, 2:], cfg, before=ext[:, 2:4])
    np.testing.assert_allclose(np.asarray(tail, np.float32)[0, :, 0], want[2:], rtol=2e-2)


@pytest.mark.parametrize("ablation", [
    "a_row_late", "taps_reversed", "no_gate_c", "no_qk_norm", "bias_in_weights",
    "no_bias", "no_topk_eps"])
def test_reference_comparison_detects(params, exact, monkeypatch, ablation):
    """The comparison sees each thing the configuration states or assumes."""
    cfg, p = CFG, params
    if ablation == "a_row_late":
        CONTROLS["conv_row_late"](monkeypatch)
    elif ablation == "taps_reversed":
        p = {**params, **{n: {**params[n], "conv_w": params[n]["conv_w"][..., ::-1]}
                          for n in ("conv_layers", "conv_dense_layers")}}
    elif ablation == "no_gate_c":
        w = params["conv_layers"]["conv_in"]["w"]
        p = {**params, "conv_layers": {**params["conv_layers"], "conv_in": {
            "w": w.at[:, :, 32:64].set(w[:, :, :32])}}}
    elif ablation == "no_qk_norm":
        CONTROLS["no_qk_norm"](monkeypatch)
    elif ablation == "bias_in_weights":
        CONTROLS["bias_in_weights"](monkeypatch)
    elif ablation == "no_bias":
        p = {**params, "conv_layers": {**params["conv_layers"], "router_bias":
                                       jnp.zeros_like(params["conv_layers"]["router_bias"])}}
    else:
        cfg = dataclasses.replace(CFG, norm_topk_eps=0.05)
    got = np.asarray(dec.forward(p, cfg, jnp.asarray(IDS)[None]))[0]
    assert np.abs(got - _reference(params, IDS)).max() > 50 * EXACT


def test_the_bias_moves_the_choice_and_never_the_weight(params):
    """Selection by score + bias, weights from the scores alone, normalised
    over the chosen with + 1e-6: the program's router against a hand count."""
    lp = jax.tree_util.tree_map(lambda a: a[0], {
        k: v for k, v in params["conv_layers"].items() if k != "experts"})
    y = jax.random.normal(jax.random.PRNGKey(2), (40, 32), jnp.float32)
    cw, load = dec.route_topk(lp, y, CFG)
    scores = np.asarray(jax.nn.sigmoid(y @ lp["router"]["w"]), np.float64)
    chosen = np.argsort(-(scores + np.asarray(lp["router_bias"])), axis=-1)[:, :2]
    unbiased = np.argsort(-scores, axis=-1)[:, :2]
    assert (np.sort(chosen, -1) != np.sort(unbiased, -1)).any()   # it decides
    want = np.zeros((40, 8))
    for t in range(40):
        picked = scores[t, chosen[t]]
        want[t, chosen[t]] = picked / (picked.sum() + 1e-6)
    np.testing.assert_allclose(np.asarray(cw), want, atol=2e-6)
    assert int(load.sum()) == 80
    # the eps is the configuration's: 1e-6 here, 1e-20 where none is stated
    assert (np.asarray(cw).sum(-1) < 1.0 - 5e-7).all()
    plain, _ = dec.route_topk(lp, y, dataclasses.replace(CFG, norm_topk_eps=1e-20))
    np.testing.assert_allclose(np.asarray(plain).sum(-1), 1.0, atol=2e-7)


# -- through pages and conv rows ---------------------------------------------------


def _through_the_cache(cfg, params, rows, lens, new, chunk, kern, pages_per=10,
                       dtype=jnp.float32):
    """Chunked prefill of three ragged rows (row r in slot r), then lockstep
    decode steps fed the rows' own tokens: every step's logits, a row at a
    time, each step's counters, and the pools at the end."""
    kept = jnp.asarray(np.random.RandomState(2).permutation(
        np.arange(1, 1 + 3 * pages_per)).reshape(3, pages_per), jnp.int32)
    kp, vp = jax.tree_util.tree_map(
        lambda a: a.astype(dtype), init_page_pool(cfg, 1 + 3 * pages_per, PAGE, slots=3))
    chunked = jax.jit(lambda p, *a, ssm_rows: paged_prefill_chunk(
        p, cfg, *a, ssm_rows=ssm_rows, **kern))
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
                jnp.asarray([len(c)]), kept[r:r + 1], kp, vp,
                ssm_rows=jnp.asarray([r + 1]))
            counts.append((len(c), [int(v) for v in stats]))
        got[r].append(np.asarray(logits)[0])
    cur = np.asarray(lens, np.int32)
    for i in range(new - 1):
        tok = jnp.asarray([rows[r][lens[r] + i] for r in range(3)])
        logits, kp, vp, stats = step(params, tok, jnp.asarray(cur),
                                     jnp.asarray([True] * 3), kept, kp, vp)
        counts.append((3, [int(v) for v in stats]))
        for r in range(3):
            got[r].append(np.asarray(logits)[r])
        cur += 1
    return [np.stack(g) for g in got], counts, (kp, vp)


LENS, NEW = [41, 26, 53], 5
ROWS = [np.random.RandomState(21 + r).randint(1, 128, n + NEW).astype(np.int32)
        for r, n in enumerate(LENS)]


@KERNELS
@pytest.mark.parametrize("name,chunk", [("h64", 8), ("h64", 12), ("h64", 20),
                                        ("h32", 12)])
def test_chunked_prefill_then_decode_matches_reference(all_params, exact, name,
                                                       chunk, kern):
    """Rows of 41, 26 and 53 tokens in chunks of 8, 12 and 20 (their last
    chunks 1 to 13 positions of padding, chunk boundaries off the page
    grid), then decode: the logits of every step are the reference's
    full-forward logits and the counters a hand count, through plain XLA
    and through the Pallas kernels (the narrow-head walk over row-major
    pools, the expert product); the conv windows left are the reference's
    gated inputs of the last two positions fed."""
    cfg, p = CONFIGS[name], all_params[name]
    got, counts, (kp, _) = _through_the_cache(cfg, p, ROWS, LENS, NEW, chunk, kern)
    hp = ref.hyper(cfg)
    for r, n in enumerate(LENS):
        want = _reference(p, ROWS[r][:n + NEW - 1], cfg)
        np.testing.assert_allclose(got[r], want[n - 1:], atol=EXACT)
        with jax.default_matmul_precision("highest"):
            gated = ref.decoder_logits(p, jnp.asarray(ROWS[r][:n + NEW - 1]), 0,
                                       new=1, hp=hp, state_at=n + NEW - 3)[2]
        np.testing.assert_allclose(np.asarray(kp["conv"][:, r + 1]),
                                   np.asarray(gated), atol=EXACT)
    for n, (pairs, hit, load) in counts:
        assert pairs == n * 2 * 5 and 0 < hit <= 8 * 5 and 0 < load <= n


@KERNELS
@pytest.mark.parametrize("name,chunk", [("h64", 12), ("h64", 20), ("h32", 12)])
def test_a_chunk_riding_the_decode_step_matches_reference(all_params, exact, name,
                                                          chunk, kern):
    """``paged_fused_step`` against the float32 reference: rows 0 and 1 are
    prefilled, then every chunk of row 2 (53 tokens: whole chunks and a
    short last one, the first from a slot whose conv rows hold NOISE) RIDES
    a decode step of rows 0 and 1, fed their own tokens; then all three
    decode. Every step's logits — the lanes' and, behind the prompt's last
    chunk, its own — are the reference's full-forward logits, the counters
    by row range a hand count, and the conv windows left are the reference's
    gated inputs of the last two positions fed."""
    cfg, p = CONFIGS[name], all_params[name]
    lens, pages_per = [41, 26, 53], 10
    rides = -(-lens[2] // chunk)
    new = rides + 2
    rows = [np.random.RandomState(61 + r).randint(1, 128, n + new).astype(np.int32)
            for r, n in enumerate(lens)]
    kept = jnp.asarray(np.random.RandomState(2).permutation(
        np.arange(1, 1 + 3 * pages_per)).reshape(3, pages_per), jnp.int32)
    kp, vp = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        init_page_pool(cfg, 1 + 3 * pages_per, PAGE, slots=3))
    kp = {**kp, "conv": jnp.full_like(kp["conv"], 7.0)}       # earlier tenants
    chunked = jax.jit(lambda p, *a, ssm_rows: paged_prefill_chunk(
        p, cfg, *a, ssm_rows=ssm_rows, **kern))
    fused = jax.jit(lambda p, *a, ssm_rows: pd.paged_fused_step(
        p, cfg, *a, return_logits=True, ssm_rows=ssm_rows, **kern))
    step = jax.jit(lambda p, *a: paged_decode_step(
        p, cfg, *a, return_logits=True, **kern))

    def padded(r, off):
        c = rows[r][off:min(off + chunk, lens[r])]
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :len(c)] = c
        return jnp.asarray(ids), jnp.asarray([off]), jnp.asarray([len(c)])

    got = [[] for _ in lens]
    for r in (0, 1):
        for off in range(0, lens[r], chunk):
            logits, kp, vp, _ = chunked(p, *padded(r, off), kept[r:r + 1], kp, vp,
                                        ssm_rows=jnp.asarray([r + 1]))
        got[r].append(np.asarray(logits)[0])
    cur = np.asarray(lens, np.int32)
    for i in range(new - 1):
        act = np.asarray([True, True, i >= rides])
        tok = jnp.asarray([rows[r][lens[r] + i - (rides if r == 2 else 0)] if act[r]
                           else 0 for r in range(3)])
        operands = (tok, jnp.asarray(np.where(act, cur, 0)), jnp.asarray(act), kept)
        if i < rides:
            ids, off, clen = padded(2, i * chunk)
            logits, kp, vp, stats = fused(p, *operands, ids, off, clen, kept[2:3],
                                          kp, vp, ssm_rows=jnp.asarray([3]))
            pairs, hit, load = np.asarray(stats).T
            per = 2 * cfg.expert_layers
            assert list(pairs) == [2 * per, int(clen[0]) * per,
                                   (2 + int(clen[0])) * per]
            assert max(hit[:2]) <= hit[2] <= hit[0] + hit[1]
            if i == rides - 1:  # the prompt's own next token
                got[2].append(np.asarray(logits)[3])
        else:
            logits, kp, vp, _ = step(p, *operands, kp, vp)
        for r in np.flatnonzero(act):
            got[r].append(np.asarray(logits)[r])
        cur += act
    hp = ref.hyper(cfg)
    for r, n in enumerate(lens):
        fed = n + len(got[r]) - 1
        want = _reference(p, rows[r][:fed], cfg)
        np.testing.assert_allclose(np.stack(got[r]), want[n - 1:], atol=EXACT)
        with jax.default_matmul_precision("highest"):
            gated = ref.decoder_logits(p, jnp.asarray(rows[r][:fed]), 0, new=1,
                                       hp=hp, state_at=fed - 2)[2]
        np.testing.assert_allclose(np.asarray(kp["conv"][:, r + 1]),
                                   np.asarray(gated), atol=EXACT)


@KERNELS
@pytest.mark.parametrize("into", [1, 2, 3])
def test_a_prompt_that_ends_just_into_a_chunk_crosses_the_seam(params, exact,
                                                               kern, into):
    """A prompt 1, 2 or 3 positions into its second chunk: the chunk reads
    its window from the first chunk's last rows, leaves ``into`` rows of its
    own behind the padding, and the first decode steps read across the seam."""
    n = 12 + into
    rows = [np.random.RandomState(40 + into + r).randint(1, 128, n + 4).astype(np.int32)
            for r in range(3)]
    got, _, _ = _through_the_cache(CFG, params, rows, [n] * 3, 4, 12, kern)
    for r in range(3):
        np.testing.assert_allclose(got[r], _reference(params, rows[r][:n + 3])[n - 1:],
                                   atol=EXACT)


def test_padding_and_idle_lanes_leave_a_window_alone(params):
    """A decode step whose lane is idle, and a chunk's padded positions, do
    not move a window; a lane that is active moves its own row only."""
    _, _, (kp, vp) = _through_the_cache(CFG, params, ROWS, LENS, 2, 12, {},
                                        dtype=jnp.bfloat16)
    before = np.asarray(kp["conv"], np.float32)
    table = jnp.zeros((3, 10), jnp.int32)
    act = jnp.asarray([False, True, False])
    _, kp2, _, _ = paged_decode_step(params, CFG, jnp.asarray([5, 6, 7]),
                                     jnp.asarray(LENS) + 1, act, table, kp, vp)
    after = np.asarray(kp2["conv"], np.float32)
    np.testing.assert_array_equal(after[:, [1, 3]], before[:, [1, 3]])
    assert (after[:, 2, 1] != before[:, 2, 1]).any()
    np.testing.assert_array_equal(after[:, 2, 0], before[:, 2, 1])  # shifted by one
    # an all-padding chunk (length 0) at a nonzero offset moves nothing
    _, kp3, _, _ = paged_prefill_chunk(
        params, CFG, jnp.zeros((1, 12), jnp.int32), jnp.asarray([30]),
        jnp.asarray([0]), table[:1], kp, vp, ssm_rows=jnp.asarray([2]))
    np.testing.assert_array_equal(np.asarray(kp3["conv"], np.float32)[:, 1:],
                                  before[:, 1:])


def test_a_reused_slot_starts_from_zeros(params, exact):
    """A first chunk (offset 0) reads zeros whatever its slot's row held: the
    second tenant's logits are those of a fresh pool."""
    kp, vp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    init_page_pool(CFG, 11, PAGE, slots=2))
    dirty = {**kp, "conv": jnp.full_like(kp["conv"], 7.0)}
    table = jnp.asarray([[3, 1, 5, 7]], jnp.int32)
    ids = jnp.asarray(IDS[None, :12])
    args = (jnp.asarray([0]), jnp.asarray([12]), table)
    clean, *_ = paged_prefill_chunk(params, CFG, ids, *args, kp, vp,
                                    ssm_rows=jnp.asarray([1]))
    reused, kp2, *_ = paged_prefill_chunk(params, CFG, ids, *args, dirty, vp,
                                          ssm_rows=jnp.asarray([1]))
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(reused))
    assert (np.asarray(kp2["conv"][:, 2]) == 7.0).all()       # the other slot's
    with pytest.raises(ValueError, match="names its rows of the state pool"):
        paged_prefill_chunk(params, CFG, ids, *args, kp, vp)


def test_one_shot_prefill_refuses_a_conv_pool(params):
    kp, vp = init_page_pool(CFG, 9, PAGE, slots=2)
    with pytest.raises(ConfigError, match="pools kv, conv.*prefills in chunks"):
        paged_prefill(params, CFG, jnp.zeros((1, 16), jnp.int32), jnp.asarray([9]),
                      jnp.zeros((1, 2), jnp.int32), kp, vp)
    with pytest.raises(ConfigError, match="conv layers.*serving: continuous"):
        dec.init_kv_cache(CFG, 1, 16)


@pytest.mark.parametrize("name", list(CONFIGS))
def test_kernel_probe_holds_the_narrow_head_walk_to_gather(all_params, name):
    """The build-time probe at heads of 64 and 32: the walk over row-major
    pools (decode and a 2-token chunk, rows on scattered pages, one crossing
    a page) against the gathered context, and the expert product."""
    from arkflow_tpu.tpu.serving_core import logits_parity

    out = gqa_kernel_probe(all_params[name], CONFIGS[name], PAGE, kernel_interpret=True)
    assert [n for n, _, _ in out] == ["paged_attention_decode",
                                      "paged_attention_chunk", "expert_product"]
    for n, want, got in out:
        assert want.shape == got.shape and logits_parity(want, got)["ok"], n


# -- programs this PR must not move ---------------------------------------------------

#: sha256 (first 16 hex) of the jaxprs (source positions stripped) of a decode
#: step and a prefill chunk of tiny models of the K-EXAONE layout (a window
#: pattern, QK norm, routed experts with a shared one) and the MiMo layout
#: (sizes by kind, a key in two parts, a sink, a held share), at heads of 128
#: lanes' multiples through the Pallas kernels: recorded at PR 46's parent.
#: The Mistral, Falcon-H1 and latent layouts' stand in
#: ``tests/test_window_gqa_moe.py`` (``WINDOW_0_GOLDEN``, ``BYPASS_GOLDEN``)
#: PR 60 RE-RECORDED all four: it changed the
#: kernel's body on purpose (both products take the type the pools hold; a
#: chunk tile reads a head's rows out of the slot's own words), so every
#: program that holds ``_paged_kernel`` moved and nothing else did (both layouts call it on
#: every attention layer)
#: PR 61 RE-RECORDED all four (2ca563491bcd87c4, 4571e4f2f4d62228 (kexaone), 3479e88feaf87a78, 4ec43e19441a44be (mimo) before it): its walk is ``_page_walk``'s (a run of ``PAGE_RUN`` neighbours a copy out of pools that
#: ride as flat rows, a program's last step starting the next program's first
#: group): every program that holds ``_paged_kernel`` moved — a window call's
#: too, whose walk takes no runs but shares the copies and the hand-on — and
#: nothing else did
ROUTED_GOLDEN = {
    "kexaone.decode": "a05b400e52d0fbc0", "kexaone.chunk": "3bf729e722e5e392",
    "mimo.decode": "e89ec1dfbce0698e", "mimo.chunk": "6444332fe89b5db7"}


def _routed_text(case: str) -> str:
    layout, step = case.split(".")
    sizes = dict(vocab_size=64, dim=32, layers=3, heads=4, kv_heads=2, head_dim=128,
                 ffn=48, max_seq=64, layer_types=("sliding_attention",
                                                  "full_attention",
                                                  "sliding_attention"),
                 sliding_window=9, n_routed_experts=8, num_experts_per_tok=2,
                 n_shared_experts=1, moe_intermediate_size=16,
                 first_k_dense_replace=1, qk_norm=True, full_attention_rope=False)
    if layout == "mimo":
        sizes.update(qk_norm=False, full_attention_rope=True, n_shared_experts=0,
                     head_dim=192, v_head_dim=128, swa_v_head_dim=128,
                     swa_kv_heads=4, partial_rotary_factor=0.334,
                     attention_value_scale=0.707, swa_rope_theta=1e4,
                     add_swa_attention_sink_bias=True, experts_held=(4, 4))
    cfg = dec.DecoderConfig(**sizes)
    p = jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg))
    kp, vp = jax.eval_shape(lambda: init_page_pool(cfg, 9, 8, 9))
    kw = dict(attention_kernel="paged", kernel_interpret=False)

    def tables(rows, tokens):
        return (jnp.zeros((rows, 4), jnp.int32), jnp.zeros(
            (rows, pd.window_ring_pages(cfg, 8, tokens)), jnp.int32))

    if step == "decode":
        jaxpr = jax.make_jaxpr(lambda p, k, v: paged_decode_step(
            p, cfg, jnp.zeros((2,), jnp.int32), jnp.ones((2,), jnp.int32),
            jnp.ones((2,), bool), tables(2, 1), k, v, **kw))(p, kp, vp)
    else:
        jaxpr = jax.make_jaxpr(lambda p, k, v: paged_prefill_chunk(
            p, cfg, jnp.zeros((1, 8), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.full((1,), 5, jnp.int32), tables(1, 8), k, v, **kw))(p, kp, vp)
    return re.sub(r"0x[0-9a-f]+", "0x", re.sub(r" at [^\s\]]+:\d+", "", str(jaxpr)))


@pytest.mark.parametrize("case", sorted(ROUTED_GOLDEN))
def test_routed_per_head_programs_do_not_move(case):
    text = _routed_text(case)
    assert "paged_window_attention" in text
    got = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert got == ROUTED_GOLDEN[case], got


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
           for s, n in ((1, 44), (2, 23), (3, 61), (4, 9), (5, 17))]


def test_the_server_runs_ahead_and_reuses_slots():
    """Five prompts over three slots: the model is stateful and, no EOS
    being live, runs one step ahead of the device (with one it serves in
    lockstep); each request's tokens are those of a server it has to
    itself."""
    proc = _proc()
    server = proc._server
    assert server._stateful and server._ahead and not server._layered

    async def run(srv, prompts):
        return await asyncio.gather(*[srv.generate(p, 6) for p in prompts])

    outs = asyncio.run(run(server, PROMPTS))
    alone = [asyncio.run(run(_proc()._server, [p]))[0] for p in PROMPTS[3:]]
    assert outs[3:] == alone and [len(o) for o in outs] == [6] * 5
    assert max(t[2] for t in server._state_tenant) >= 2       # a slot was reused
    assert server._steps_ahead > 0
    assert len(server._free_pages) == server.num_pages - 1
    st = server.slot_state(0)
    assert st["state"].shape == (5, 2, 32) and st["tenancy"] >= 1


def test_server_counters_and_gauges_equal_a_hand_count():
    """One prompt of 21 tokens (chunks of 8: 8 + 8 + 5) and 6 new tokens."""
    proc = _proc()
    server = proc._server
    names = ("arkflow_gen_moe_assignments_total", "arkflow_gen_ssm_tokens_total",
             "arkflow_gen_ssm_masked_total")
    before = {(n, k): _counter(n, kind=k).value
              for n in names for k in ("chunk", "decode")}
    resets = server.m_ssm_resets.value
    out = asyncio.run(server.generate(
        np.random.RandomState(1).randint(1, 128, 21).tolist(), 6))
    assert len(out) == 6
    d = {key: _counter(key[0], kind=key[1]).value - v for key, v in before.items()}
    # 5 expert layers, 2 choices a token
    assert d[names[0], "chunk"] == 21 * 2 * 5 and d[names[0], "decode"] == 5 * 2 * 5
    assert d[names[1], "chunk"] == 21 and d[names[2], "chunk"] == 3
    assert d[names[1], "decode"] == 5 and d[names[2], "decode"] == 5 * 2
    assert server.m_ssm_resets.value - resets == 1
    # the gauges read the spec: a page of K/V rows over the 2 attention
    # layers, a slot's windows over the 5 conv layers
    assert [g[1] for g in server.m_kv_live] == ["pages", "slots"]
    assert [g[2] for g in server.m_kv_live] == [PAGE * 2 * 2 * 128 * 2, 5 * 2 * 32 * 2]
    assert {m.labels["pool"] for m in global_registry().collect()
            if m.name == "arkflow_gen_kv_live_bytes"} >= {"kv", "conv"}
    assert global_registry().gauge(
        "arkflow_gen_kv_bytes_per_token",
        labels={"model": "decoder_lm"}).value == kv_bytes_per_token(CFG) == 1024


@pytest.mark.parametrize("name", list(CONFIGS))
def test_the_paged_server_passes_its_probe_and_counts_its_walk(name):
    cfg = CONFIGS[name]
    proc = _proc({"head_dim": cfg.head_dim, "kv_heads": cfg.kv_heads},
                 decode_kernel="paged", kernel_interpret=True)
    server = proc._server
    parity = server.kernel_parity
    assert parity["ok"] and parity["kernels"] == [
        "paged_attention_decode", "paged_attention_chunk", "expert_product"]
    walked = _counter("arkflow_gen_attn_pages_walked_total", kind="decode").value
    tiles = {k: m.value for k, m in server.m_attn_tiles.items()}
    out = asyncio.run(server.generate(PROMPTS[1], 4))
    assert len(out) == 4
    assert _counter("arkflow_gen_attn_pages_walked_total", kind="decode").value > walked
    got = {k: m.value - tiles[k] for k, m in server.m_attn_tiles.items()}
    # 23 tokens: 3 chunks, then 3 decode steps of 3 lanes; 2 attention layers;
    # a row-major pool's walk multiplies a run of heads' own rows, zero-
    # extended: a product of its own name, no per-head tile
    assert got == {("chunk", "head_run"): 3 * 2, ("decode", "head_run"): 3 * 3 * 2,
                   **{(kind, product): 0 for kind in ("chunk", "decode")
                      for product in ("per_kv_head", "all_heads")}}


# -- what is served and what is still refused ---------------------------------------


@pytest.mark.parametrize("extra,needle", [
    ({"mesh": {"tp": 2}}, "pools kv, conv.*one chip"),
    ({"serving": "batch"}, "pools kv, conv.*serving: continuous"),
    ({"prefill_chunk": 0}, "pools kv, conv.*prefill_chunk > 0"),
    ({"prefix_cache_pages": 8}, "prefix_cache_pages.*pools kv, conv"),
    ({"speculative_tokens": 2}, "speculative_tokens.*pools kv, conv"),
    ({"swap": {"watch": "/nowhere"}}, "swap is not supported"),
    ({"integrity": {"interval": "1s"}}, "integrity is not supported"),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else None)
def test_the_model_refuses_what_is_not_served_with_it(extra, needle):
    with pytest.raises(ConfigError, match=needle):
        _proc(**extra)


def test_the_model_refuses_kv_push_by_its_pools():
    proc = _proc()
    assert getattr(proc, "disagg", None) is None
    with pytest.raises(ConfigError, match="pools kv, conv.*no wire form"):
        asyncio.run(proc._server.prefill_export([1, 2, 3], 2))


def test_a_narrow_head_s_pages_cross_kv_push_with_their_head_axis():
    """A dense model of narrow heads (row-major pools) exports page slabs
    [layers, pages, page, kv heads, width] and adopts them: the wire form
    kept its head axis."""
    cfg = {"type": "tpu_generate", "model": "decoder_lm",
           "model_config": dict(vocab_size=64, dim=32, layers=2, heads=4,
                                kv_heads=2, ffn=64),
           "serving": "continuous", "max_input": 32, "max_new_tokens": 4,
           "slots": 2, "page_size": PAGE, "seq_buckets": [16], "eos_id": -1,
           "decode_kernel": "gather", "seed": 1}
    a, b = (build_component("processor", cfg, Resource()) for _ in range(2))
    assert a._server.k_pages.shape == (2, a._server.num_pages, PAGE, 2 * 8)
    prompt = list(range(1, 12))
    export = asyncio.run(a._server.prefill_export(prompt, 4))
    assert export["k"][0].shape == (2, 2, PAGE, 2, 8)
    got = asyncio.run(b._server.generate_from_pages(export))
    assert got == asyncio.run(a._server.generate(prompt, 4))


# -- the judge and the controls it refuses ------------------------------------------


def _set(monkeypatch, target, name, value):
    (monkeypatch.setattr if monkeypatch is not None else setattr)(target, name, value)


def _conv_row_late(monkeypatch=None):
    """The conv reads v_{t-1} .. v_{t-3}: a row late."""
    real = dec.short_conv

    def late(lp, y, cfg, before=None):
        shifted = {**lp, "conv_w": jnp.concatenate(
            [lp["conv_w"][..., 1:], jnp.zeros_like(lp["conv_w"][..., :1])], -1)}
        return real(shifted, y, cfg, before)

    for mod in (dec, pd):
        _set(monkeypatch, mod, "short_conv", late)


def _no_qk_norm(monkeypatch=None):
    real = dec.qk_positioned

    def unnormed(lp, q, k, cfg, positions, kind=FULL):
        return real(lp, q, k, dataclasses.replace(cfg, qk_norm=False), positions, kind)

    for mod in (dec, pd):
        _set(monkeypatch, mod, "qk_positioned", unnormed)


def _bias_in_weights(monkeypatch=None):
    """The expert bias added to the weights, not to the selection alone."""
    real = dec.route_topk

    def biased(lp, y, cfg, token_mask=None):
        scores = jax.nn.sigmoid(jnp.dot(
            y.astype(jnp.float32), lp["router"]["w"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)) + lp["router_bias"]
        _, load = real(lp, y, cfg, token_mask)
        k = cfg.num_experts_per_tok
        _, idx = jax.lax.top_k(scores, k)
        w = jnp.take_along_axis(scores, idx, axis=-1)
        w = w / (w.sum(-1, keepdims=True) + cfg.norm_topk_eps)
        live = (jnp.ones(y.shape[:1]) if token_mask is None
                else token_mask.reshape(-1).astype(jnp.float32))
        return jnp.einsum("tk,tke->te", w, jax.nn.one_hot(
            idx, cfg.n_routed_experts)) * live[:, None], load

    _set(monkeypatch, dec, "route_topk", biased)


def _state_survives(monkeypatch=None):
    """A prompt's first chunk does not reset its slot's window."""
    real = pd._conv_paged

    def kept(lp, y, cfg, windows, layer, rows, fresh, valid):
        return real(lp, y, cfg, windows, layer, rows,
                    None if fresh is None else jnp.zeros_like(fresh), valid)

    _set(monkeypatch, pd, "_conv_paged", kept)


def _coarse(x):
    """``x`` rounded to 3 mantissa bits (e4m3's)."""
    m, e = jnp.frexp(x.astype(jnp.float32))
    return jnp.ldexp(jnp.round(m * 16) / 16, e).astype(x.dtype)


def _mantissa3(monkeypatch=None):
    """Every product's left operand at 3 mantissa bits: the projections'
    inputs (``cm.dense``) and the expert products'."""
    dense, routed = cm.dense, dec.routed_mlp

    def coarse_dense(p, x, *args, **kw):
        return dense(p, _coarse(x), *args, **kw)

    def coarse_routed(lp, y, cfg, **kw):
        return routed(lp, _coarse(y), cfg, **kw)

    _set(monkeypatch, cm, "dense", coarse_dense)
    for mod in (dec, pd):
        _set(monkeypatch, mod, "routed_mlp", coarse_routed)


def _heads_crossed(monkeypatch=None, every: int = 1, beyond: int = 0):
    """The walk hands a query head its NEIGHBOUR K/V head's keys and values
    (a head read from another's lanes) at every ``every``-th position from
    ``beyond`` on: a fault of a later page group, which the server's own
    start-up probe (a few pages) does not reach."""
    real = pd._attend_paged

    def crossed(q, k_pages, v_pages, layer, page_table, off, cfg, *args, **kw):
        group = cfg.heads // cfg.kv_heads
        wrong = jnp.roll(real(jnp.roll(q, group, axis=2), k_pages, v_pages, layer,
                              page_table, off, cfg, *args, **kw), -group, axis=2)
        right = real(q, k_pages, v_pages, layer, page_table, off, cfg, *args, **kw)
        at = off[:, None] + jnp.arange(q.shape[1])[None]
        return jnp.where(((at % every == 0) & (at >= beyond))[..., None, None],
                         wrong, right)

    _set(monkeypatch, pd, "_attend_paged", crossed)


#: the controls the builder runs on the chip through the timed path (a driver
#: applies one, then runs the benchmark's cell): each must be REFUSED. The
#: last is what the cell's ``correct`` is NOT sized to see (PERF.md §7): a
#: walk that mis-serves one position in ten
CONTROLS = {"mantissa3": _mantissa3, "conv_row_late": _conv_row_late,
            "state_survives": _state_survives, "bias_in_weights": _bias_in_weights,
            "no_qk_norm": _no_qk_norm,
            "heads_crossed_beyond_1k": functools.partial(_heads_crossed, beyond=1024),
            "heads_crossed_one_in_ten_beyond_1k": functools.partial(
                _heads_crossed, every=10, beyond=1024)}


def _greedy(params, cfg, prompt, new, width=96):
    """The PROGRAM's greedy continuation of ``prompt`` (its forward in
    float32 over a padded row: causal layers never look at the padding)."""
    row, n = np.zeros((1, width), np.int32), len(prompt)
    row[0, :n] = prompt
    fwd = jax.jit(lambda p, x: dec.forward(p, cfg, x))
    with jax.default_matmul_precision("highest"):
        for _ in range(new):
            row[0, n] = int(np.asarray(fwd(params, jnp.asarray(row)))[0, n - 1].argmax())
            n += 1
    return row[0, len(prompt):n].tolist()


JUDGED = [IDS[:40].tolist(), IDS[10:58].tolist(), IDS[5:35].tolist()]


def test_judge_accepts_the_program_s_tokens_and_refuses_others(params, exact):
    tokens = [_greedy(params, CFG, p, 6) for p in JUDGED[:2]]
    hp = ref.hyper(CFG)
    good = ref.judge_rows(params, hp, JUDGED[:2], tokens, longest=96, shares=0.02)
    assert good["ok"] and good["unexplained"] == 0 and good["rerouted"] == 0
    assert good["positions_checked"] == 12
    wrong = [[(t + 1) % 128 for t in toks] for toks in tokens]
    bad = ref.judge_rows(params, hp, JUDGED[:2], wrong, longest=96)
    assert not bad["ok"] and bad["unexplained"] > 0


@pytest.mark.parametrize("control", ["mantissa3", "conv_row_late", "no_qk_norm"])
def test_judge_refuses_the_control(params, exact, monkeypatch, control):
    """Tokens the program serves under a control are not the reference's.
    The cell's limits are sized for bfloat16 products behind ten expert
    layers on the chip; here the products are float32 and the program
    itself reads 0 (the test above), so the limits are held at a fiftieth."""
    CONTROLS[control](monkeypatch)
    tokens = [_greedy(params, CFG, p, 8) for p in JUDGED]
    verdict = ref.judge_rows(params, ref.hyper(CFG), JUDGED, tokens, longest=96,
                             shares=0.02)
    assert not verdict["ok"]
    assert verdict["unexplained_share"] > 2 * 0.02 * ref.UNEXPLAINED_SHARE


def test_judge_says_where_the_unexplained_lie_and_shows_their_cause(params, exact):
    """Beside the verdict: the unexplained positions by row and by quarter
    of a row's tokens, and the witness — the forward held to ITSELF under
    the choices the rules admit (a margin wide enough that the tiny model
    has some)."""
    tokens = [_greedy(params, CFG, p, 8) for p in JUDGED[:2]]
    hp = ref.hyper(CFG)
    good = ref.judge_rows(params, hp, JUDGED[:2], tokens, longest=96, delta=0.05)
    assert good["ok"] and good["unexplained_by_row"] == [[0, 8, 40], [0, 8, 48]]
    assert good["unexplained_by_quarter"] == [0, 0, 0, 0]
    wit = good["witness"]
    assert wit["flipped"] > 0 and 0 <= wit["unexplained_first_round"] <= wit["moved"] <= 16
    assert wit["unexplained_first_round_share"] == wit["unexplained_first_round"] / 16
    wrong = [[(t + 1) % 128 for t in toks] for toks in tokens]
    bad = ref.judge_rows(params, hp, JUDGED[:2], wrong, longest=96)
    assert sum(r[0] for r in bad["unexplained_by_row"]) == bad["unexplained"] \
        == sum(bad["unexplained_by_quarter"]) > 0
    assert bad["unexplained_first_round_share"] >= bad["unexplained_share"]
    # re-routed positions are counted and limit nothing
    assert "REROUTED_SHARE" not in vars(ref)


def test_the_limit_holds_over_each_row_of_its_own(params, exact, monkeypatch):
    """A fault on ONE row (the long one's contexts) that the share over all
    positions would pass: the limit holds over each judged row too, from
    ``ROW_POSITIONS`` tokens up."""
    tokens = [_greedy(params, CFG, p, 8) for p in JUDGED[:2]]
    # the LAST three: teacher forcing feeds them to no later position
    spoiled = [tokens[0], tokens[1][:5] + [(t + 1) % 128 for t in tokens[1][5:]]]
    hp = ref.hyper(CFG)
    monkeypatch.setattr(ref, "ROW_POSITIONS", 8)
    # three of a row's eight positions, three of sixteen over all: a limit of
    # a quarter passes the whole and refuses the row
    shares = 0.25 / ref.UNEXPLAINED_SHARE
    bad = ref.judge_rows(params, hp, JUDGED[:2], spoiled, longest=96, shares=shares)
    assert [r[0] for r in bad["unexplained_by_row"]] == [0, 3] and not bad["ok"]
    monkeypatch.setattr(ref, "ROW_POSITIONS", 64)     # rows too short to hold
    assert ref.judge_rows(params, hp, JUDGED[:2], spoiled, longest=96,
                          shares=shares)["ok"]


def test_judge_serves_a_cut_with_no_router(exact):
    """The witness's other half: the program with NO router (every FFN the
    dense SwiGLU, the same walk, windows and seam) is judged by rule (a)
    alone and reads 0 unexplained; nothing to re-route, no witness."""
    cut = dataclasses.replace(CFG, first_k_dense_replace=0, n_routed_experts=0,
                              num_experts_per_tok=0, moe_intermediate_size=0,
                              router_bias_std=0.0)
    weights = dec.init(jax.random.PRNGKey(3), cut)
    hp = ref.hyper(cut)
    assert hp["dense"] == 7 and not hp["moe"] and not any(hp["experts_ahead"])
    tokens = [_greedy(weights, cut, p, 6) for p in JUDGED[:2]]
    verdict = ref.judge_rows(weights, hp, JUDGED[:2], tokens, longest=96, shares=0.02)
    assert verdict["ok"] and verdict["unexplained"] == 0 and verdict["witness"] is None
    assert verdict["router_near_tie_share"] == 0 and verdict["reroute_forwards"] == 0
    wrong = [[(t + 1) % 128 for t in toks] for toks in tokens]
    assert not ref.judge_rows(weights, hp, JUDGED[:2], wrong, longest=96)["ok"]


def test_judge_holds_the_window_behind_one_router(params):
    """Rule (d) behind the first attention layer: the conv layer behind ONE
    router is held where the forward routes the window's positions far from
    a tie there (a margin of nothing: every row), and is not where it does
    not (a margin of one: no row)."""
    hp = ref.hyper(CFG)
    assert [j for j, a in enumerate(hp["experts_ahead"]) if a == 1] == [2]
    prompt, toks = JUDGED[0], IDS[40:46].tolist()
    with jax.default_matmul_precision("highest"):
        gated = np.asarray(ref.decoder_logits(
            params, jnp.asarray(prompt + toks[:-1]), 0, new=1, hp=hp,
            state_at=len(prompt) + 3)[2])
    off = gated.copy()
    off[2] *= 1.5                       # only the layer behind one router
    held = dict(shares=1e9, delta=1e-9)
    ok = ref.judge_rows(params, hp, [prompt], [toks], 96, states=[gated], **held)
    bad = ref.judge_rows(params, hp, [prompt], [toks], 96, states=[off], **held)
    assert ok["ok"] and ok["rows_held_behind_one_router"] == 1
    assert ok["state_rel_err_behind_one_router"] < 1e-5
    assert not bad["ok"] and bad["state_rel_err"] < 1e-5
    assert bad["state_rel_err_behind_one_router"] == pytest.approx(0.5, rel=1e-3)
    loose = ref.judge_rows(params, hp, [prompt], [toks], 96, states=[off],
                           shares=1e9, delta=1.0)
    assert loose["ok"] and loose["rows_held_behind_one_router"] == 0


def test_judge_holds_the_windows_a_row_left(params):
    """Rule (d): the window a row left is the forward's gated inputs of the
    last two positions it FED; a window a position late is refused."""
    hp = ref.hyper(CFG)
    prompt, toks = JUDGED[0], IDS[40:46].tolist()
    row = jnp.asarray(prompt + toks[:-1])
    with jax.default_matmul_precision("highest"):
        gated = np.asarray(ref.decoder_logits(
            params, row, 0, new=1, hp=hp, state_at=len(prompt) + 3)[2])
        late = np.asarray(ref.decoder_logits(
            params, row, 0, new=1, hp=hp, state_at=len(prompt) + 2)[2])
    assert gated.shape == (5, 2, 32)
    ok = ref.judge_rows(params, hp, [prompt], [toks], 96, states=[gated], shares=1e9)
    bad = ref.judge_rows(params, hp, [prompt], [toks], 96, states=[late], shares=1e9)
    assert ok["state_rel_err"] < 1e-5 and ok["ok"]
    assert bad["state_rel_err"] > 10 * ref.STATE_REL_ERR and not bad["ok"]


def _probe(params_of=None):
    proc = _proc(slots=1)
    server = proc._server
    for p in PROMPTS[:2]:
        asyncio.run(server.generate(p, 3))
    return ref.reuse_probe(server, proc.params, ref.hyper(proc.cfg), 7, 128)


def test_the_reuse_probe_sees_a_state_that_survives(monkeypatch):
    """Rule (e): after two requests over one slot, a one-token prompt's
    chunk leaves zeros and its own gated input, held behind the expert
    layers its routing is far from a tie at; under the control the earlier
    tenant's row is still there."""
    hp = ref.hyper(CFG)
    assert hp["experts_ahead"] == (0, 0, 1, 2, 3)
    good = _probe()
    assert good["ok"] and good["tenancy"] == 3 and good["before_abs_max"] == 0.0
    assert good["gated_rel_err"] < ref.STATE_REL_ERR
    assert good["conv_layers_held"] >= 3 and good["expert_layers_robust"] >= 1
    CONTROLS["state_survives"](monkeypatch)
    bad = _probe()
    assert not bad["ok"] and bad["before_abs_max"] > 0.0


def _served_row(every=None):
    """A row through the served path (chunks, then decode through pages and
    conv rows, the narrow-head walk interpreted), judged with its window."""
    proc = _proc(decode_kernel="paged", kernel_interpret=True, slots=1)
    server = proc._server
    asyncio.run(server.generate(PROMPTS[0], 6))
    st = server.slot_state(0)
    return ref.judge_rows(
        proc.params, ref.hyper(proc.cfg), [list(st["prompt"])], [list(st["tokens"])],
        96, states=[np.asarray(st["state"], np.float32)], shares=1e9, delta=1e-9)


@pytest.mark.parametrize("every", [1, 2])
def test_the_window_behind_one_router_sees_a_walk_that_crosses_heads(monkeypatch, every):
    """Rule (d) behind the first attention layer is continuous in what the
    walk returned: query heads handed their neighbour K/V head's lanes from
    position 40 on (past the server's start-up probe, which refuses the
    same fault within its few pages) move the window there by most of its
    norm, whatever the routers do; the windows ahead of every router and a
    sound walk's stay at the bfloat16 products' rounding."""
    good = _served_row()
    assert good["ok"] and good["rows_held_behind_one_router"] == 1
    assert good["state_rel_err_behind_one_router"] < ref.STATE_REL_ERR
    _heads_crossed(monkeypatch, every=every, beyond=40)
    bad = _served_row()
    assert not bad["ok"] and bad["state_rel_err"] < ref.STATE_REL_ERR
    assert bad["state_rel_err_behind_one_router"] > 10 * ref.STATE_REL_ERR
    # within the probe's pages the program refuses to serve it
    _heads_crossed(monkeypatch, every=every)
    with pytest.raises(ConfigError, match="disagrees with the dense gather reference"):
        _proc(decode_kernel="paged", kernel_interpret=True, slots=1)


@pytest.mark.parametrize("control", ["bias_in_weights", "mantissa3"])
def test_the_probe_sees_what_the_tokens_cannot(monkeypatch, control):
    """Behind a router the gated input of the probe's token carries the
    experts' weights: products at 3 mantissa bits move it past the limit;
    the bias in the weights moves it along a line the reference knows."""
    good = _probe()
    assert good["ok"] and abs(good["bias_in_weights_share"]) < 0.2
    CONTROLS[control](monkeypatch)
    bad = _probe()
    assert not bad["ok"] and bad["before_abs_max"] == 0.0
    if control == "mantissa3":
        assert bad["gated_rel_err"] > 1.5 * ref.STATE_REL_ERR
    else:  # less than the products' rounding moves them: the direction tells
        assert bad["gated_rel_err"] < ref.STATE_REL_ERR
        assert 0.8 < bad["bias_in_weights_share"] < 1.2


def test_judge_holds_the_float32_leaves():
    masters = dec.init(jax.random.PRNGKey(3), CFG)
    placed = jax.tree_util.tree_map(lambda leaf, dt: leaf.astype(dt), masters,
                                    dec.serve_dtypes(CFG))
    assert ref.stated_float32_leaves_differ(placed, masters) == 0
    placed["conv_layers"]["router_bias"] = placed["conv_layers"][
        "router_bias"].astype(jnp.bfloat16)
    assert ref.stated_float32_leaves_differ(placed, masters) == 3 * 8
