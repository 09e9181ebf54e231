"""Several residual streams mixed by manifold-constrained hyper-connections
around YaRN-extended latent attention and sigmoid-routed experts (the
Xing4.0 layout), held to the plain reference ``benchmark/references/
mhc_mla_moe.py`` on seeded weights at tiny widths: the full forward, chunks
then decode through the paged cache, the mixing's kernels (interpreted)
against their plain form, YaRN's table against hand-worked values, the
expert-parallel shares, the refusals, the programs that must not move, and
six controls that must each FAIL the comparison they are aimed at.

``exact`` runs the program's products in float32 at ``highest`` precision:
what is left between it and the reference is the order of float32 sums
(``EXACT``), and every control lands far above it. The kernels' path runs in
bfloat16 as served and is held to the reference's own logit tolerance off
the router's near-ties, as ``tests/test_mla_moe.py`` does.
"""

from __future__ import annotations

import asyncio
import dataclasses
import importlib.util
import math
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
from arkflow_tpu.models.paged_decode import (init_page_pool, kv_bytes_per_token,
                                             latent_kernel_probe,
                                             paged_decode_step,
                                             paged_prefill_chunk)
from arkflow_tpu.obs import global_registry
from arkflow_tpu.ops import mhc_mix as mm

ensure_plugins_loaded()


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/references/mhc_mla_moe.py", "ref_mhc_mla_moe")

#: YaRN at a size where the test rows pass the original positions: with a
#: rope width of 8 and 16 original positions pair 0 keeps its frequency and
#: pairs 1..3 turn 64 times slower (``low`` 0, ``high`` 1)
YARN = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
TINY = dict(vocab_size=128, dim=128, layers=3, heads=4, ffn=64, max_seq=256,
            rope_theta=1e4, norm_eps=1e-6, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8, q_lora_rank=12, rope_interleave=True,
            n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
            moe_intermediate_size=16, first_k_dense_replace=1,
            routed_scaling_factor=2.0, hc_mult=4, hc_sinkhorn_iters=20,
            hc_eps=1e-6, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
            rope_scaling=YARN)
CFG = dec.DecoderConfig(**TINY)
PAGE = 8
INTERPRET = dict(attention_kernel="paged", kernel_interpret=True)
EXACT = 2e-4
IDS = np.random.RandomState(11).randint(1, 128, 40).astype(np.int32)


def _placed(cfg, seed=7):
    """Seeded weights rounded as the processor places them, float32 again."""
    return jax.tree_util.tree_map(
        lambda leaf, dt: leaf.astype(dt).astype(jnp.float32),
        dec.init(jax.random.PRNGKey(seed), cfg), dec.serve_dtypes(cfg))


@pytest.fixture(scope="module")
def params():
    return _placed(CFG)


@pytest.fixture
def exact(monkeypatch):
    """The program's products in float32 at ``highest`` precision."""
    monkeypatch.setattr(cm.dense, "__defaults__", (jnp.float32,))
    monkeypatch.setattr(cm.embedding, "__defaults__", (jnp.float32,))
    with jax.default_matmul_precision("highest"):
        yield


def _reference(params, ids, cfg=CFG):
    """Reference logits [S, vocab] and router margins [S] over one row."""
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(lambda p, x: ref.decoder_logits(
            p, x, 0, new=len(ids), hp=ref.hyper(cfg)))
        logits, (near, _) = fn(params, jnp.asarray(ids))
    near = np.asarray(near)
    return np.asarray(logits), (near[..., 1] - near[..., 2]).min(-1)


# -- the forward and the cache against the reference ---------------------------------


def _forward(params, cfg, ids):
    return jax.jit(lambda p, x: dec.forward(p, cfg, x))(params, jnp.asarray(ids)[None])[0]


def test_forward_matches_reference(params, exact):
    got = np.asarray(_forward(params, CFG, IDS))
    want, _ = _reference(params, IDS)
    np.testing.assert_allclose(got, want, atol=EXACT)


def _tables(n_rows, pages_per):
    perm = np.random.RandomState(2).permutation(np.arange(1, 1 + n_rows * pages_per))
    return jnp.asarray(perm.reshape(n_rows, pages_per).astype(np.int32))


def _through_the_cache(params, rows, lens, new, chunk, kern, dtype):
    """Chunked prefill of ragged rows, then lockstep decode steps fed the
    rows' own tokens: every step's logits, a row at a time."""
    pages_per = -(-(max(lens) + new) // PAGE)
    table = _tables(len(lens), pages_per)
    kp, vp = (a.astype(dtype) for a in init_page_pool(
        CFG, 1 + len(lens) * pages_per, PAGE))
    chunked = jax.jit(lambda p, *a: paged_prefill_chunk(p, CFG, *a, **kern))
    step = jax.jit(lambda p, *a: paged_decode_step(
        p, CFG, *a, return_logits=True, **kern))
    got = [[] for _ in lens]
    for r, n in enumerate(lens):
        for off in range(0, n, chunk):
            c = rows[r][off:min(off + chunk, n)]
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :len(c)] = c
            logits, kp, vp, stats = chunked(
                params, jnp.asarray(ids), jnp.asarray([off]),
                jnp.asarray([len(c)]), table[r:r + 1], kp, vp)
        got[r].append(np.asarray(logits)[0])
    cur = np.asarray(lens, np.int32)
    for i in range(new - 1):
        tok = jnp.asarray([rows[r][lens[r] + i] for r in range(len(lens))])
        logits, kp, vp, stats = step(params, tok, jnp.asarray(cur),
                                     jnp.asarray([True] * len(lens)), table, kp, vp)
        assert int(stats[0]) == len(lens) * 2 * 2  # lanes x top-2 x expert layers
        for r in range(len(lens)):
            got[r].append(np.asarray(logits)[r])
        cur += 1
    return [np.stack(g) for g in got]


LENS, NEW = [19, 26, 41], 4
ROWS = [np.random.RandomState(21 + r).randint(1, 128, n + NEW).astype(np.int32)
        for r, n in enumerate(LENS)]


@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_prefill_then_decode_matches_reference(params, exact, chunk):
    """Three ragged rows over non-contiguous pages, past YaRN's original
    positions: chunks, then lockstep decode steps; every step's logits are
    the reference's full-forward logits."""
    got = _through_the_cache(params, ROWS, LENS, NEW, chunk, {}, jnp.float32)
    for r, n in enumerate(LENS):
        want, _ = _reference(params, ROWS[r][:n + NEW - 1])
        np.testing.assert_allclose(got[r], want[n - 1:], atol=EXACT)


def test_the_kernels_path_matches_reference_in_bfloat16(params):
    """As served: bfloat16 streams and pools, the latent kernel and BOTH
    mixing kernels interpreted (a chunk of 128 rows is one token tile; the
    decode steps' three lanes take the plain form), within twice the
    reference's logit tolerance off the router's near-ties."""
    n, new = 150, 3
    row = np.random.RandomState(5).randint(1, 128, n + new).astype(np.int32)
    got = _through_the_cache(params, [row], [n], new, 128, INTERPRET, jnp.bfloat16)[0]
    want, margin = _reference(params, row[:n + new - 1])
    want, margin = want[n - 1:], margin[n - 1:]
    keep = margin >= 4e-3
    tol = 2 * ref.logit_tolerance(want)
    assert keep.any() and np.abs(got - want)[keep].max() <= tol


def test_the_kernels_are_what_a_chunk_of_a_tile_runs(params):
    text = str(jax.make_jaxpr(lambda p, k, v: paged_prefill_chunk(
        p, CFG, jnp.zeros((1, 128), jnp.int32), jnp.zeros((1,), jnp.int32),
        jnp.full((1,), 128, jnp.int32), jnp.zeros((1, 20), jnp.int32), k, v,
        **INTERPRET))(params, *init_page_pool(CFG, 21, PAGE)))
    assert "mhc_pre" in text and "mhc_post" in text
    step = str(jax.make_jaxpr(lambda p, k, v: paged_decode_step(
        p, CFG, jnp.zeros((3,), jnp.int32), jnp.ones((3,), jnp.int32),
        jnp.ones((3,), bool), jnp.zeros((3, 20), jnp.int32), k, v,
        **INTERPRET))(params, *init_page_pool(CFG, 21, PAGE)))
    assert "pallas_call" in step and "name=mhc_pre" not in step  # the plain form


# -- the mixing itself --------------------------------------------------------------

KW = dict(n=4, iters=20, eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6)


def _streams(t, n=4, c=256, seed=1):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    leaves = jax.tree_util.tree_map(lambda a: a[0], dec.init(
        jax.random.PRNGKey(seed), dataclasses.replace(CFG, dim=c))["layers"]["mhc_attn"])
    x = (3 * jax.random.normal(k1, (t, n * c))).astype(jnp.bfloat16)
    return leaves, x, jax.random.normal(k2, (t, c)).astype(jnp.bfloat16)


@pytest.mark.parametrize("tokens", [130, 32, 20])
def test_mixing_kernels_equal_their_plain_form(tokens):
    """Interpreted: a whole tile and a ragged one behind it, a decode step's
    lanes as ONE short tile, and rows that do not fill a sublane tile."""
    leaves, x, y = _streams(tokens, c=128)
    u0, h0 = jax.jit(lambda x: mm.mhc_pre_xla(x, leaves, **KW))(x)
    u1, h1 = jax.jit(lambda x: mm.mhc_pre_kernel(x, leaves, interpret=True, **KW))(x)
    assert h1.shape == (tokens, 128) and h1.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(h1[:, :24]), np.asarray(h0), atol=2e-6)
    np.testing.assert_allclose(np.asarray(u1, np.float32), np.asarray(u0, np.float32),
                               atol=2 ** -6)  # a bfloat16 rounding's worth
    out0 = jax.jit(mm.mhc_post_xla)(x, y, h0)
    out1 = jax.jit(lambda *a: mm.mhc_post_kernel(*a, interpret=True))(x, y, h1)
    np.testing.assert_allclose(np.asarray(out1, np.float32),
                               np.asarray(out0, np.float32), atol=2 ** -5)
    assert mm.kernel_serves(x[:16], 4) and not mm.kernel_serves(x[:15], 4)
    assert not mm.kernel_serves(x.astype(jnp.float32), 4)


def test_phi_splits_exactly_into_three_bfloat16_terms():
    phi = jax.random.normal(jax.random.PRNGKey(3), (512, 24), jnp.float32) / 23
    parts = np.asarray(mm.split_phi(phi, 4), np.float32)
    assert parts.shape == (512, 128) and not parts[:, 72:].any()
    np.testing.assert_array_equal(
        parts[:, :24] + parts[:, 24:48] + parts[:, 48:72], np.asarray(phi))


def test_mixing_matrix_is_doubly_stochastic_and_not_trivial():
    leaves, x, _ = _streams(64)
    pre, post, res = mm.mhc_split(mm.mhc_pre_xla(x, leaves, **KW)[1], 4)
    res = np.asarray(res)
    assert np.abs(res.sum(-1) - 1).max() < 1e-4 and np.abs(res.sum(-2) - 1).max() < 1e-4
    assert (res > 0).all()
    diag = res[:, np.arange(4), np.arange(4)].mean()
    assert 0.25 < diag < 0.8                      # neither the identity nor uniform
    assert res.std(axis=0).mean() > 0.02         # a token's own, not one matrix
    assert 0 < np.asarray(pre).min() and np.asarray(pre).max() < 1
    assert 0 < np.asarray(post).min() and np.asarray(post).max() < 2
    # one pass of the normalisation is not the twenty the config states
    one = np.asarray(mm.mhc_split(mm.mhc_pre_xla(
        x, leaves, **{**KW, "iters": 1})[1], 4)[2])
    assert np.abs(one.sum(-2) - 1).max() > 1e-2


def test_mixing_equals_the_reference_token_by_token():
    leaves, x, y = _streams(9)
    hp = {"hc_mult": 4, "eps": 1e-6, "hc_iters": 20, "hc_eps": 1e-6,
          "hc_clamp": (-30.0, 30.0)}
    with jax.default_matmul_precision("highest"):
        xf, yf = x.astype(jnp.float32), y.astype(jnp.float32)
        rows = xf.reshape(9, 4, -1)           # the reference's view of a token
        want = jax.vmap(lambda t: ref.coefficients(leaves, t, hp))(rows)
        got = mm.mhc_split(mm.mhc_pre_xla(xf, leaves, **KW)[1], 4)
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-6)
        u, h = mm.mhc_pre_xla(xf, leaves, **KW)
        back = jax.vmap(ref.mix_back)(rows, yf, want[1], want[2])
        np.testing.assert_allclose(np.asarray(mm.mhc_post_xla(xf, yf, h)),
                                   np.asarray(back).reshape(9, -1), atol=1e-4)


# -- YaRN ---------------------------------------------------------------------------

PUBLISHED = {"type": "yarn", "factor": 64, "original_max_position_embeddings": 4096,
             "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}


def test_yarn_table_equals_hand_worked_values():
    """d 64, base 1e4, 4,096 original positions: corr(32) = 10.47 and
    corr(1) = 22.5, so pairs 0..10 keep their frequency, pairs 23..31 turn 64
    times slower and pair 16 is 6 / 13 of the way."""
    freqs, mult = dec.rope_frequencies(64, 10000, tuple(sorted(PUBLISHED.items())))
    freqs = np.asarray(freqs)
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    assert mult == 1.0
    np.testing.assert_allclose(freqs[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(freqs[23:], plain[23:] / 64, rtol=1e-6)
    ramp = 6 / 13
    np.testing.assert_allclose(freqs[16], plain[16] * (ramp / 64 + 1 - ramp), rtol=1e-6)
    np.testing.assert_allclose(freqs, ref.yarn_frequencies(64, 10000.0, PUBLISHED),
                               rtol=1e-6)
    assert math.isclose(dec.yarn_softmax_mult(tuple(sorted(PUBLISHED.items()))),
                        (0.1 * math.log(64) + 1) ** 2) and math.isclose(
                            0.1 * math.log(64) + 1, 1.4159, abs_tol=1e-4)
    sp = dec.DecoderConfig(**{**TINY, "rope_scaling": PUBLISHED}).attn(dec.FULL)
    assert math.isclose(sp.softmax_scale, 16 ** -0.5 * 1.4159 ** 2, rel_tol=1e-4)
    # mscale != mscale_all_dim scales cos and sin
    lop = dict(PUBLISHED, mscale=0.707)
    assert math.isclose(dec.rope_frequencies(64, 10000, tuple(sorted(lop.items())))[1],
                        (0.0707 * math.log(64) + 1) / 1.4159, rel_tol=1e-4)


@pytest.mark.parametrize("rot", ["_rope", "_rope_interleaved"])
def test_both_rotations_read_the_one_table_beyond_the_original_positions(rot):
    scaling = tuple(sorted(PUBLISHED.items()))
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, 2, 64), jnp.float32)
    pos = jnp.asarray([[5, 4096, 150000]])
    got = np.asarray(getattr(dec, rot)(x, pos, 10000, scaling))
    freqs = ref.yarn_frequencies(64, 10000.0, PUBLISHED)
    ang = np.asarray(pos)[0][:, None].astype(np.float64) * freqs[None].astype(np.float64)
    xs = np.asarray(x)[0]
    for t in range(3):
        c, s = np.cos(ang[t]), np.sin(ang[t])
        a, b = ((xs[t][:, 0::2], xs[t][:, 1::2]) if rot == "_rope_interleaved"
                else (xs[t][:, :32], xs[t][:, 32:]))
        want = (np.stack([a * c - b * s, a * s + b * c], -1).reshape(2, 64)
                if rot == "_rope_interleaved"
                else np.concatenate([a * c - b * s, a * s + b * c], -1))
        np.testing.assert_allclose(got[0, t], want, atol=2e-2 if t == 2 else 1e-3)
    plain = np.asarray(getattr(dec, rot)(x, pos, 10000))
    assert np.abs(plain - got)[0, 1:].max() > 0.5      # another rotation there
    np.testing.assert_allclose(plain[0, 0, :, :2], got[0, 0, :, :2], atol=1e-6)


# -- the expert-parallel shares ------------------------------------------------------


def test_the_four_shares_of_an_expert_layer_add_up(params, exact):
    """Four shares of 2 of the 8 experts, the shared expert and the mixing
    counted once, are the uncut layer: in the program and in the reference."""
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 9, 4 * 128), jnp.float32)
    u, h = dec.hc_pre(lp["mhc_mlp"], x, CFG)
    y = cm.rms_norm(lp["mlp_norm"], u, CFG.norm_eps)
    whole, load = dec.routed_mlp(lp, y, CFG)
    assert int(load.sum()) == 2 * 9 * 2

    def part(first, count=2):
        cfg = dataclasses.replace(CFG, experts_held=(first, count))
        ex = {k: jnp.concatenate([v[first:first + count], v[8:]])
              for k, v in lp["experts"].items()}
        return dec.routed_mlp({**lp, "experts": ex}, y, cfg)[0]

    def shared():
        ex = {k: jnp.concatenate([jnp.zeros_like(v[:1]), v[8:]])
              for k, v in lp["experts"].items()}
        return dec.routed_mlp({**lp, "experts": ex}, y,
                              dataclasses.replace(CFG, experts_held=(0, 1)))[0]

    parts = sum(part(first) for first in range(0, 8, 2)) - 3 * shared()
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), atol=EXACT)
    got = dec.hc_post(x, parts, h)
    hp = ref.hyper(CFG)
    rlp = {**lp, "experts": (jax.tree_util.tree_map(lambda a: a[None], lp["experts"]), 0)}
    want = ref.sub_layer(lp["mhc_mlp"], x.reshape(18, 4, 128), hp, lambda u: (
        ref.routed_experts(rlp, ref._rms_norm(lp["mlp_norm"]["scale"], u,
                                              hp["eps"]), hp)[0]))
    np.testing.assert_allclose(np.asarray(got).reshape(18, 4, 128),
                               np.asarray(want), atol=EXACT)  # stream j: columns j C ..
    # a held share through the whole forward: the reference follows
    held = dataclasses.replace(CFG, experts_held=(2, 2))
    p = _placed(held)
    np.testing.assert_allclose(np.asarray(_forward(p, held, IDS)),
                               _reference(p, IDS, held)[0], atol=EXACT)


# -- controls: each must FAIL the comparison -----------------------------------------


def _set(monkeypatch, target, name, value):
    (monkeypatch.setattr if monkeypatch is not None else setattr)(target, name, value)


def _coefficients_with(monkeypatch, change):
    """``change`` applied to the coefficients BOTH forms compute (a list, a
    coefficient an entry: the kernel's own statement, and the plain form's
    rows)."""
    rows, listed = mm._coefficients_rows, mm._coefficients

    def changed_rows(h, *, n, **kw):
        return jnp.stack(change(list(rows(h, n=n, **kw)), n))

    def changed_list(h, *, n, **kw):
        return change(listed(h, n=n, **kw), n)

    _set(monkeypatch, mm, "_coefficients_rows", changed_rows)
    _set(monkeypatch, mm, "_coefficients", changed_list)


def _identity_res(coef, n):
    eye = [jnp.full_like(coef[0], float(i == j)) for i in range(n) for j in range(n)]
    return coef[:2 * n] + eye


def _post_without_the_2(coef, n):
    return coef[:n] + [c / 2 for c in coef[n:2 * n]] + coef[2 * n:]


def _bf16_coefficients(coef, n):
    # (a cast to bfloat16 and back is dropped by the chip's compiler)
    return [jax.lax.reduce_precision(c, exponent_bits=8, mantissa_bits=7) for c in coef]


def _one_iteration(monkeypatch=None):
    pre = dec.hc_pre

    def once(leaves, x, cfg, **form):
        return pre(leaves, x, dataclasses.replace(cfg, hc_sinkhorn_iters=1), **form)

    for mod in (dec, sys.modules["arkflow_tpu.models.paged_decode"]):
        _set(monkeypatch, mod, "hc_pre", once)


def _plain_rope(monkeypatch=None):
    real = dec.rope_frequencies
    _set(monkeypatch, dec, "rope_frequencies",
         lambda d, theta, scaling=None: real(d, theta, None))


def _coarse(x):
    """``x`` rounded to 3 mantissa bits (e4m3's)."""
    m, e = jnp.frexp(x.astype(jnp.float32))
    return jnp.ldexp(jnp.round(m * 16) / 16, e).astype(x.dtype)


def _mantissa3(monkeypatch=None):
    """Every product's left operand at 3 mantissa bits: the projections'
    inputs (``cm.dense``) and the expert products' — the nearest precision
    below the bfloat16 the configuration states."""
    dense, routed = cm.dense, dec.routed_mlp
    _set(monkeypatch, cm, "dense",
         lambda p, x, *args, **kw: dense(p, _coarse(x), *args, **kw))
    for mod in (dec, sys.modules["arkflow_tpu.models.paged_decode"]):
        _set(monkeypatch, mod, "routed_mlp",
             lambda lp, y, cfg, **kw: routed(lp, _coarse(y), cfg, **kw))


#: name -> (what is changed in the PROGRAM, the least multiple of ``EXACT`` the
#: forward's logits must move off the reference's). The builder applies one
#: to a process before ``benchmark/run.py`` to see the cell's judge refuse it
CONTROLS = {
    "hres_identity": (lambda mp=None: _coefficients_with(mp, _identity_res), 25),
    "hpost_without_the_2": (
        lambda mp=None: _coefficients_with(mp, _post_without_the_2), 25),
    "one_sinkhorn_iteration": (_one_iteration, 25),
    "plain_rope_beyond_L": (_plain_rope, 25),
    "scale_without_g2": (lambda mp=None: _set(mp, dec, "yarn_softmax_mult",
                                              lambda scaling: 1.0), 25),
    "bf16_coefficients": (
        lambda mp=None: _coefficients_with(mp, _bf16_coefficients), 5),
    "mantissa3": (_mantissa3, 25),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_reference_comparison_detects(params, exact, monkeypatch, control):
    """The comparison of ``test_forward_matches_reference`` is not vacuous:
    the program run WITH the named departure disagrees with the reference by
    far more than the tolerance (rows of 40 tokens, past the 16 original
    positions)."""
    apply, least = CONTROLS[control]
    apply(monkeypatch)
    got = np.asarray(_forward(params, CFG, IDS))
    want, _ = _reference(params, IDS)
    assert np.abs(got - want).max() > least * EXACT, control


# -- config, refusals, dtypes, programs that must not move ----------------------------


def test_config_reads_every_key_and_names_what_it_refuses():
    assert CFG.hc_mult == 4 and CFG.hc_res_clamp == (-30.0, 30.0)
    assert dict(CFG.rope_scaling)["factor"] == 64 and hash(CFG) is not None
    bad = [
        ({"kv_lora_rank": 0, "rope_interleave": False, "n_routed_experts": 0,
          "rope_scaling": None}, "latent attention"),
        ({"layer_types": ["full_attention", "sliding_attention", "full_attention"],
          "sliding_window": 8, "swa_heads": 2, "swa_kv_lora_rank": 8,
          "swa_qk_nope_head_dim": 8, "swa_qk_rope_head_dim": 4,
          "swa_v_head_dim": 8, "swa_rope_theta": 1e4, "rope_scaling": None},
         "full_attention latent layers only"),
        ({"rope_scaling": dict(YARN, type="linear")}, "'linear' is not served"),
        ({"rope_scaling": {"type": "yarn", "factor": 4}}, "missing"),
        ({"hc_sinkhorn_iters": 0}, "hc_sinkhorn_iters >= 1"),
        ({"remat": True}, "served, not trained"),
    ]
    for extra, needle in bad:
        with pytest.raises(ConfigError, match=needle):
            dec.DecoderConfig(**{**TINY, **extra})
    with pytest.raises(ConfigError, match="latent-attention model's full layers"):
        dec.DecoderConfig(vocab_size=64, dim=32, layers=2, heads=4, kv_heads=2,
                          ffn=48, rope_scaling=YARN)


def test_embed_init_std_seeds_the_table_and_nothing_else():
    """The table's seeded spread is a key (0.02 unless stated; the cell's
    file states 0.5 so that a token's own embedding decides its routing):
    the same draws at another size, every other leaf bit for bit."""
    import json

    key = jax.random.PRNGKey(5)
    usual = dec.init(key, CFG)
    wide = dec.init(key, dataclasses.replace(CFG, embed_init_std=0.5))
    assert dataclasses.replace(CFG, embed_init_std=0.02) == CFG
    np.testing.assert_allclose(np.asarray(wide["embed"]["table"]) / 25.0,
                               np.asarray(usual["embed"]["table"]), rtol=1e-6)
    assert abs(float(np.asarray(wide["embed"]["table"]).std()) - 0.5) < 0.02
    for name in usual:
        if name != "embed":
            for a, b in zip(jax.tree_util.tree_leaves(usual[name]),
                            jax.tree_util.tree_leaves(wide[name])):
                assert np.array_equal(np.asarray(a), np.asarray(b)), name
    conf = json.loads((ROOT / "benchmark/configs/xing4.0-29b-a4b-l10-ep4.json").read_text())
    sizes = {**conf, **conf["rehearse"]["model"]}
    cfg = dec.DecoderConfig(**{ours: sizes[theirs] for ours, theirs
                               in conf["model_config_from"].items()})
    assert cfg.embed_init_std == conf["embed_init_std"] == 0.5
    assert "embed_init_std" in conf["assumed"]


def _proc(**extra):
    cfg = {"type": "tpu_generate", "model": "decoder_lm", "model_config": TINY,
           "serving": "continuous", "max_input": 40, "max_new_tokens": 4,
           "slots": 4, "page_size": PAGE, "seq_buckets": [16], "prefill_chunk": 16,
           "eos_id": -1, "decode_kernel": "gather", "seed": 3, **extra}
    return build_component("processor", cfg, Resource())


@pytest.mark.parametrize("extra,needle", [
    ({"mesh": {"tp": 2}}, "hc_mult 4 .* one chip"),
    ({"speculative_tokens": 3}, "speculative_tokens does not compose with hc_mult"),
    ({"prefix_cache_pages": 8}, "prefix_cache_pages does not compose with hc_mult"),
    ({"serving": "batch"}, "serving: continuous"),
])
def test_the_server_refuses_what_the_streams_are_not_served_with(extra, needle):
    with pytest.raises(ConfigError, match=needle):
        _proc(**extra)


def test_generates_through_tpu_generate_and_the_judge_accepts_it():
    """The normal path: continuous batching, chunks and decode steps ahead,
    the paged latent cache. Its tokens pass the reference's judge; the
    gauges say what a token's residual costs; kv_push stays refused."""
    proc = _proc()
    server = proc._server
    prompts = [np.random.RandomState(s).randint(1, 128, n).tolist()
               for s, n in ((1, 36), (2, 9), (3, 20))]

    async def run():
        return await asyncio.gather(*[server.generate(p, 4) for p in prompts])

    tokens = [list(t) for t in asyncio.run(run())]
    assert all(len(t) == 4 for t in tokens)
    verdict = ref.judge_rows(proc.params, ref.hyper(proc.cfg), prompts, tokens,
                             longest=44, shares=25.0)
    assert verdict["ok"] and verdict["positions_checked"] == 12, verdict
    assert ref.mixing_leaves_differ(proc.params, proc.host_params) == 0
    rounded = jax.tree_util.tree_map(lambda a: a, proc.params)
    rounded["layers"] = dict(rounded["layers"], mhc_mlp=jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), rounded["layers"]["mhc_mlp"]))
    assert ref.mixing_leaves_differ(rounded, proc.host_params) > 0
    reg = global_registry()
    labels = {"model": "decoder_lm"}
    assert reg.gauge("arkflow_gen_residual_streams", labels=labels).value == 4
    assert reg.gauge("arkflow_gen_residual_bytes_per_token",
                     labels=labels).value == 4 * 128 * 2
    assert kv_bytes_per_token(proc.cfg) == 3 * (16 + 128) * 2  # rope keys as held
    with pytest.raises(ConfigError, match="no head axis"):
        asyncio.run(server.prefill_export([1, 2, 3], 2))


def test_placed_leaves_are_float32_and_the_probe_holds_both_kernels():
    proc = _proc()
    for name in ("mhc_attn", "mhc_mlp"):
        for stack in ("dense_layers", "layers"):
            assert all(leaf.dtype == jnp.float32 for leaf in
                       jax.tree_util.tree_leaves(proc.params[stack][name]))
    assert proc.params["layers"]["mhc_attn"]["phi"].shape == (2, 512, 24)
    out = latent_kernel_probe(proc.params, proc.cfg, PAGE, kernel_interpret=True)
    names = [n for n, _, _ in out]
    assert names[-3:] == ["mhc_pre", "mhc_post", "expert_product"], names
    from arkflow_tpu.tpu.serving_core import logits_parity

    for name, want, got in out:
        if name.startswith("mhc_"):
            assert logits_parity(want, got)["ok"], name


_window = _load("tests/test_window_gqa_moe.py", "window_goldens")


@pytest.mark.parametrize("case", sorted(_window.BYPASS_GOLDEN))
def test_programs_of_one_stream_do_not_move(case):
    """``hc_mult`` 1 and ``rope_scaling`` null take the code they took: the
    jaxprs of a tiny kanana2_l6 layout and a tiny dots3_l5 layout (the
    indexer's ``_rope`` and both latent rotations through the shared
    frequency table) hash to what they hashed to before this model."""
    _window.test_latent_programs_do_not_move_with_the_per_head_kernel(case)


@pytest.mark.parametrize("case", ["dense.decode.paged", "dense.chunk.gather",
                                  "hybrid.decode.gather"])
def test_per_head_programs_rotate_as_before(case):
    """``_rope``'s callers outside the latent layers, through the table too."""
    _window.test_window_0_gives_the_present_jaxpr(case)
