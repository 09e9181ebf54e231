"""Kimi Delta Attention layers (a float32 matrix state a sequence, decayed a
key CHANNEL) beside position-free latent (MLA) layers, a leading dense SwiGLU
and sigmoid-routed experts of which a share is held (the Kimi-Linear-48B-A3B
layout), through the paged serving path, held to the plain reference
``benchmark/references/kda_mla_moe.py`` on seeded weights at tiny widths: six
layers K K M K K M, four heads of 16, pages of 8, a float32 state and three
rows of conv window a slot beside the latent pages (Pallas in interpret mode).

The equations are held EXACTLY: with the program's products switched to
float32 (``exact``) its logits are the reference's to 2e-4 through the full
forward and through chunked prefill and decode over latent pages and state
rows, choices included. The per-channel delta rule's chunked form is held to
the recurrence token by token (at a decay of e^-30 a token and of e^-1e-4
too: no exponent above 0 is taken), each kernel to its plain form, and the
served program to the reference's judge, which refuses each control the
builder runs on the chip (``CONTROLS``; ``tools/kda_control.py`` applies one
to a process before ``benchmark/run.py``).
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
import importlib.util
import json
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
from arkflow_tpu.models.paged_decode import (FEATURES, cache_rows, cache_spec,
                                             init_page_pool, kv_bytes_per_token,
                                             paged_decode_step, paged_prefill,
                                             paged_prefill_chunk, unserved)
from arkflow_tpu.obs import global_registry
from arkflow_tpu.ops import gdn_scan as gs
from arkflow_tpu.ops import kda_scan as ks
# the same harness as the other linear mixer's: chunked prefill then decode
# over float32 pools, the program's own greedy continuation, a patch that a
# driver applies without pytest
from tests.test_gdn_gqa_moe import _greedy, _set, _through_the_cache

ensure_plugins_loaded()


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/references/kda_mla_moe.py", "ref_kda_mla_moe")

FULL, LINEAR = dec.FULL, dec.LINEAR
KINDS = (LINEAR, LINEAR, FULL, LINEAR, LINEAR, FULL)
KDA = {"num_heads": 4, "head_dim": 16, "short_conv_kernel_size": 4,
       "kda_layers": [1, 2, 4, 5, 7, 8], "full_attn_layers": [3, 6, 9]}
TINY = dict(vocab_size=128, dim=32, layers=6, heads=4, ffn=64, max_seq=256,
            norm_eps=1e-5, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, mla_use_nope=True,
            layer_types=KINDS, linear_attn_config=KDA, n_routed_experts=16,
            num_experts_per_tok=3, n_shared_experts=1, moe_intermediate_size=16,
            first_k_dense_replace=1, routed_scaling_factor=2.446)
CFG = dec.DecoderConfig(**TINY)
PAGE = 8
INTERPRET = dict(attention_kernel="paged", kernel_interpret=True)
KERNELS = pytest.mark.parametrize("kern", [{}, INTERPRET], ids=["gather", "paged"])


def _params(cfg):
    """Seeded weights as placed."""
    return jax.tree_util.tree_map(
        lambda leaf, dt: leaf.astype(dt).astype(jnp.float32),
        dec.init(jax.random.PRNGKey(3), cfg), dec.serve_dtypes(cfg))


@pytest.fixture(scope="module")
def params():
    return _params(CFG)


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
IDS = np.random.RandomState(5).randint(1, 128, 80).astype(np.int32)


# -- the second linear mixer: config, cache spec, layer runs -------------------------


def test_linear_attn_config_is_what_says_kda():
    """ONE key states the mixer: ``linear_attn_config`` present is Kimi Delta
    Attention, ``linear_num_*`` a Gated DeltaNet; a latent tree carries
    linear layers in stacks of their own, one of them in the DENSE stack."""
    assert CFG.kda and CFG.linear and CFG.latent and CFG.stateful and CFG.by_runs
    assert not CFG.attn(FULL).rotate and CFG.linear_taps == 4
    assert dec.layer_runs(CFG) == [
        ("kda_dense_layers", 0, 1, LINEAR, False, 0),
        ("kda_layers", 0, 1, LINEAR, True, 1), ("layers", 0, 1, FULL, True, 0),
        ("kda_layers", 1, 3, LINEAR, True, 2), ("layers", 1, 2, FULL, True, 1)]
    p = dec.init(jax.random.PRNGKey(0), CFG)
    assert set(p) == {"embed", "norm_out", "lm_head", "kda_dense_layers",
                      "kda_layers", "layers"}
    assert "wq" not in p["kda_layers"] and "kda_qkv" not in p["layers"]
    assert p["kda_layers"]["kda_qkv"]["w"].shape == (3, 32, 3 * 64)
    assert p["kda_layers"]["kda_conv_w"].shape == (3, 192, 4)
    assert p["kda_layers"]["kda_dt_bias"].shape == (3, 64)
    assert p["kda_layers"]["kda_A_log"].shape == (3, 4)
    assert "w_gate" in p["kda_dense_layers"] and "experts" in p["kda_layers"]
    served = dec.serve_dtypes(CFG)
    assert jax.tree_util.tree_structure(served) == jax.tree_util.tree_structure(p)
    for leaf in ("kda_A_log", "kda_dt_bias", "router_bias"):
        assert served["kda_layers"][leaf] == jnp.float32
    assert served["kda_layers"]["kda_norm"]["scale"] == jnp.float32
    assert served["kda_layers"]["router"]["w"] == jnp.float32
    assert served["kda_layers"]["kda_qkv"]["w"] == jnp.bfloat16
    # the seeded decays: a step log-uniform in [1e-3, 1e-1] a channel, A in
    # [1, 16] a head: a channel forgets in tens to thousands of tokens
    g = -np.exp(np.asarray(p["kda_layers"]["kda_A_log"]))[..., None] * np.asarray(
        jax.nn.softplus(p["kda_layers"]["kda_dt_bias"])).reshape(3, 4, 16)
    assert -1.7 < g.min() and g.max() < -9e-4


def test_cache_spec_states_a_state_pool_beside_latent_pages():
    latent, kda = cache_spec(CFG)
    assert (latent.name, latent.layers, latent.widths) == ("latent", 2, (16, 128))
    assert (kda.name, kda.layers, kda.per_slot) == ("kda", 4, True)
    assert kda.widths == (4 * 16 * 16, 3 * 192) and kda.itemsizes == (4, 2)
    assert kda.bytes_per_slot == 4 * (4096 + 1152) and kda.bytes_per_token == 0
    assert kv_bytes_per_token(CFG) == 2 * (16 + 128) * 2
    assert cache_rows(CFG) == ("latent", "kda")
    kp, vp = init_page_pool(CFG, 9, PAGE, slots=2)
    assert kp["latent"].shape == (2, 9, PAGE, 16) and vp["latent"].shape == (2, 9, PAGE, 128)
    assert kp["kda"].shape == (4, 3, 4, 16, 16) and kp["kda"].dtype == jnp.float32
    assert vp["kda"].shape == (4, 3, 3, 192) and vp["kda"].dtype == jnp.bfloat16
    assert pd._page_size(kp) == pd._page_size(vp) == PAGE
    # at the published sizes: 13.03 MB a slot, 2,560 B a token as held
    big = dec.DecoderConfig(**{**TINY, "dim": 2304, "heads": 32, "kv_lora_rank": 512,
                               "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                               "v_head_dim": 128, "layers": 8,
                               "layer_types": (LINEAR,) * 3 + (FULL,) + (LINEAR,) * 3 + (FULL,),
                               "linear_attn_config": {"num_heads": 32, "head_dim": 128,
                                                      "short_conv_kernel_size": 4}})
    assert [p.bytes_per_slot for p in cache_spec(big)] == [0, 13_025_280]
    assert kv_bytes_per_token(big) == 2560


@pytest.mark.parametrize("change,needle", [
    ({"linear_attn_config": None}, "linear_num_key_heads dividing"),
    ({"linear_num_key_heads": 2}, "both say what a linear_attention layer is"),
    ({"kv_lora_rank": 0, "qk_nope_head_dim": 0, "qk_rope_head_dim": 0,
      "v_head_dim": 0, "mla_use_nope": False, "kv_heads": 2},
     "Kimi Delta Attention layers.*among latent-attention layers"),
    ({"layer_types": (LINEAR, "conv", FULL, LINEAR, LINEAR, FULL),
      "conv_L_cache": 3, "linear_attn_config": dict(KDA, kda_layers=[1, 4, 5])},
     "conv layers"),
    ({"layer_types": (LINEAR, LINEAR, FULL, LINEAR, LINEAR, "sliding_attention"),
      "sliding_window": 8, "swa_heads": 4, "swa_kv_lora_rank": 16,
      "swa_qk_nope_head_dim": 8, "swa_qk_rope_head_dim": 4, "swa_v_head_dim": 8,
      "swa_rope_theta": 1e4, "linear_attn_config": dict(KDA, full_attn_layers=[3])},
     "beside sliding latent layers.*or indexed ones"),
    ({"hc_mult": 4}, "linear_attention and indexed layers carry ONE residual"),
    ({"linear_attn_config": dict(KDA, kda_layers=[1, 2, 3])}, "kda_layers.*names layers"),
    ({"linear_attn_config": dict(KDA, head_dim=0)}, "num_heads, head_dim > 0"),
    ({"layer_types": (FULL,) * 6, "linear_attn_config": dict(
        KDA, kda_layers=[], full_attn_layers=[1, 2, 3, 4, 5, 6])},
     "without a linear_attention layer"),
    ({"rope_interleave": True}, "mla_use_nope.*remove them"),
    ({"mla_use_nope": False}, "rope_interleave"),
], ids=lambda v: None if isinstance(v, str) else "-".join(v))
def test_config_refuses_in_a_sentence(change, needle):
    with pytest.raises(ConfigError, match=needle):
        dec.DecoderConfig(**{**TINY, **change})


def test_a_gated_delta_net_beside_a_latent_row_stays_refused():
    with pytest.raises(ConfigError, match="beside a latent row"):
        dec.DecoderConfig(**{**TINY, "linear_attn_config": None,
                             "linear_num_key_heads": 2, "linear_num_value_heads": 4,
                             "linear_key_head_dim": 16, "linear_value_head_dim": 16,
                             "linear_conv_kernel_dim": 4})


# -- the delta rule with a decay a key channel -----------------------------------------


def _operands(b, t, h, d, seed, g_of=lambda x: -jnp.exp(2 * x - 2)):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.square(x).sum(-1, keepdims=True) + 1e-6)
    q = unit(jax.random.normal(next(keys), (b, t, h, d))) * d ** -0.5
    k = unit(jax.random.normal(next(keys), (b, t, h, d)))
    v = jax.random.normal(next(keys), (b, t, h, d))
    g = g_of(jax.random.normal(next(keys), (b, t, h, d)))
    beta = jax.nn.sigmoid(jax.random.normal(next(keys), (b, t, h)))
    s0 = jax.random.normal(next(keys), (b, h, d, d))
    return s0, q, k, v, g, beta


def test_the_per_channel_delta_rule_by_hand_on_two_tokens():
    s0, q, k, v, g, beta = (np.asarray(a, np.float64) for a in _operands(1, 2, 1, 4, 0))
    s = s0[0, 0]
    outs = []
    for t in range(2):
        s = np.exp(g[0, t, 0])[:, None] * s          # row k times exp(g[k])
        d = beta[0, t, 0] * (v[0, t, 0] - s.T @ k[0, t, 0])
        s = s + np.outer(k[0, t, 0], d)
        outs.append(s.T @ q[0, t, 0])
    o, s_t = ks.recurrent_from(*(jnp.asarray(a, jnp.float32)
                                 for a in (s0, q, k, v, g, beta)))
    np.testing.assert_allclose(np.asarray(o[0, :, 0]), np.stack(outs), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_t[0, 0]), s, atol=1e-5)
    # with one decay for all channels it is the Gated DeltaNet's rule
    same = jnp.broadcast_to(jnp.asarray(g[..., :1], jnp.float32), g.shape)
    args = [jnp.asarray(a, jnp.float32) for a in (s0, q, k, v)]
    b32 = jnp.asarray(beta, jnp.float32)
    o_k, s_k = ks.recurrent_from(*args, same, b32)
    o_g, s_g = gs.recurrent_from(*args, same[..., 0], b32)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_g), atol=1e-6)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_g), atol=1e-6)


@pytest.mark.parametrize("t,g_of,atol", [
    (64, None, 2e-5), (128, None, 2e-5), (150, None, 2e-5),
    (150, lambda x: jnp.full_like(x, -30.0), 1e-6),
    (150, lambda x: jnp.full_like(x, -1e-4), 2e-5),
    (70, lambda x: jnp.where(x > 0, -30.0, -1e-4), 2e-5)],
    ids=["64", "128", "150", "fast", "slow", "both"])
def test_the_chunked_form_is_the_recurrence(t, g_of, atol):
    """Blocks of 64 and a ragged tail; a decay of e^-30 a token (``exp(-c)``
    would overflow float32 at the third token) and of e^-1e-4, and both in
    one head: finite and equal to the token-by-token recurrence."""
    ops = _operands(2, t, 3, 16, t, **({"g_of": g_of} if g_of else {}))
    want_o, want_s = ks.recurrent_from(*ops)
    got_o, got_s = jax.jit(ks.chunk_from)(*ops)
    assert np.isfinite(np.asarray(got_o)).all() and np.isfinite(np.asarray(got_s)).all()
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), atol=atol)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s), atol=atol)


def test_no_gate_and_no_step_leave_a_state_alone():
    s0, q, k, v, g, beta = _operands(2, 70, 2, 16, 1)
    zero_g, zero_b = jnp.zeros_like(g), jnp.zeros_like(beta)
    for form in (ks.recurrent_from, ks.chunk_from):
        _, s = form(s0, q, k, v, zero_g, zero_b)
        np.testing.assert_allclose(np.asarray(s), np.asarray(s0), atol=1e-6)


@pytest.mark.parametrize("t,g_of", [
    (1, None), (7, None), (130, None), (256, None),
    (130, lambda x: jnp.full_like(x, -30.0)),
    (130, lambda x: jnp.full_like(x, -1e-4))],
    ids=["1", "7", "130", "256", "fast", "slow"])
def test_each_kernel_is_its_plain_form(t, g_of):
    """Interpreted kernels on a pool of three rows: a decode step of two
    lanes on rows 2 and 1, a chunk of ``t`` tokens with its first row fresh
    and its last positions padded; the other rows stay bit for bit."""
    s0, q, k, v, g, beta = _operands(2, t, 4, 16, 40 + t,
                                     **({"g_of": g_of} if g_of else {}))
    pool = jax.random.normal(jax.random.PRNGKey(9), (2, 3, 4, 16, 16), jnp.float32)
    rows = jnp.asarray([2, 1], jnp.int32)
    kern = dict(kernel=True, interpret=True)
    want = ks.kda_state_update(pool, 1, rows, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                               beta[:, 0])
    got = ks.kda_state_update(pool, 1, rows, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                              beta[:, 0], **kern)
    for a, b in zip(want, got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[1][0]), np.asarray(pool[0]))
    live = (jnp.arange(t) < max(t - 3, 1))[None, :, None]
    g, beta = jnp.where(live[..., None], g, 0.0), jnp.where(live, beta, 0.0)
    fresh = jnp.asarray([True, False])
    want = ks.kda_chunk_scan(pool, 0, rows, fresh, q, k, v, g, beta)
    got = ks.kda_chunk_scan(pool, 0, rows, fresh, q, k, v, g, beta, **kern)
    for a, b in zip(want, got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got[1][1]), np.asarray(pool[1]))
    np.testing.assert_array_equal(np.asarray(got[1][0, 0]), np.asarray(pool[0, 0]))
    ref_o, ref_s = ks.recurrent_from(jnp.stack([jnp.zeros_like(pool[0, 2]), pool[0, 1]]),
                                     q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref_o), atol=5e-5)
    np.testing.assert_allclose(np.asarray(got[1][0, rows]), np.asarray(ref_s), atol=5e-5)


# -- the forward against the reference --------------------------------------------------


def test_forward_matches_reference(params, exact):
    got = np.asarray(dec.forward(params, CFG, jnp.asarray(IDS[None])))[0]
    np.testing.assert_allclose(got, _reference(params, IDS), atol=EXACT)


def _operands_control(change):
    """A control over what the delta rule reads: ``change(q, k, v, g, beta)``."""
    def apply(monkeypatch=None):
        real = dec.kda_operands

        def changed(lp, conved, b, a, cfg, valid=None):
            return change(*real(lp, conved, b, a, cfg, valid))
        _set(monkeypatch, dec, "kda_operands", changed)
    return apply


def _no_dt_bias(monkeypatch=None):
    real = dec.kda_operands

    def without(lp, *args, **kw):
        return real({**lp, "kda_dt_bias": jnp.zeros_like(lp["kda_dt_bias"])},
                    *args, **kw)
    _set(monkeypatch, dec, "kda_operands", without)


def _rotated_kr(monkeypatch=None):
    """The shared key and the queries' part that meets it ROTATED at
    ``rope_theta``, as every other latent model's are."""
    real = dec.mla_project

    def rotated(lp, y, cfg, positions, cq=None):
        return real(lp, y, dataclasses.replace(cfg, rotate=True), positions, cq)
    for mod in (dec, pd):
        _set(monkeypatch, mod, "mla_project", rotated)


def _bf16_state(monkeypatch=None):
    """The state rounded to bfloat16 at every write (a pool held in
    bfloat16, whatever its declared type)."""
    update, scan = ks.kda_state_update, ks.kda_chunk_scan

    def rounded(fn):
        def call(state, *args, **kw):
            o, state = fn(state, *args, **kw)
            # (a cast to bfloat16 and back is dropped by the chip's compiler)
            return o, jax.lax.reduce_precision(state, exponent_bits=8,
                                               mantissa_bits=7)
        return call

    _set(monkeypatch, ks, "kda_state_update", rounded(update))
    _set(monkeypatch, ks, "kda_chunk_scan", rounded(scan))


def _state_survives(monkeypatch=None):
    """A prompt's first chunk does not reset its slot's state and window."""
    real = pd._gdn_paged

    def kept(lp, y, cfg, states, windows, layer, rows, fresh, valid, *kern):
        if fresh is not None:  # (None is a decode step)
            fresh = jnp.zeros_like(fresh)
        return real(lp, y, cfg, states, windows, layer, rows, fresh, valid, *kern)

    _set(monkeypatch, pd, "_gdn_paged", kept)


def _gate_is_silu(monkeypatch=None):
    """The output gate under SiLU (the Gated DeltaNet's), not a sigmoid."""
    _set(monkeypatch, dec, "kda_output",
         lambda lp, o, z, cfg, dtype: dec.gdn_output(
             {"gdn_norm": lp["kda_norm"], "gdn_out": lp["kda_out"]}, o, z, cfg, dtype))


#: the controls the builder runs on the chip through the timed path
#: (``tools/kda_control.py`` applies one, then runs the benchmark's cell):
#: each must be REFUSED
CONTROLS = {
    "bf16_state": _bf16_state, "no_dt_bias": _no_dt_bias,
    # ONE decay a head (its channels' mean), as a Gated DeltaNet has
    "head_decay": _operands_control(lambda q, k, v, g, beta: (
        q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), beta)),
    "rotated_kr": _rotated_kr, "state_survives": _state_survives,
    "no_decay": _operands_control(lambda q, k, v, g, beta: (
        q, k, v, jnp.zeros_like(g), beta)),
    "gate_is_silu": _gate_is_silu}


@pytest.mark.parametrize("ablation", ["no_dt_bias", "head_decay", "rotated_kr",
                                      "no_decay", "gate_is_silu"])
def test_reference_comparison_detects(params, exact, monkeypatch, ablation):
    """The comparison is not vacuous: each departure from the equations
    moves the forward's logits off the reference's by far more than the
    tolerance."""
    ids = IDS[:40]
    CONTROLS[ablation](monkeypatch)
    got = np.asarray(dec.forward(params, CFG, jnp.asarray(ids[None])))[0]
    assert np.abs(got - _reference(params, ids)).max() > 25 * EXACT


def test_the_eight_shares_of_an_expert_layer_add_up(params, exact):
    """The held experts' parts over the eight shares, the shared expert
    counted once, are the uncut layer: in the program and in the reference."""
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    y = jax.random.normal(jax.random.PRNGKey(8), (2, 9, 32), jnp.float32)
    whole, load = dec.routed_mlp(lp, y, CFG)
    assert int(load.sum()) == 2 * 9 * 3
    shared_only = dataclasses.replace(CFG, experts_held=(0, 1))

    def part(first):
        """Share ``first``'s two experts, the shared one riding along."""
        cfg = dataclasses.replace(CFG, experts_held=(first, 2))
        ex = {k: jnp.concatenate([v[first:first + 2], v[16:]])
              for k, v in lp["experts"].items()}
        return dec.routed_mlp({**lp, "experts": ex}, y, cfg)[0]

    def shared():
        ex = {k: jnp.concatenate([jnp.zeros_like(v[:1]), v[16:]])
              for k, v in lp["experts"].items()}
        return dec.routed_mlp({**lp, "experts": ex}, y, shared_only)[0]

    parts = sum(part(first) for first in range(0, 16, 2)) - 7 * shared()
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), atol=EXACT)
    rlp = {**lp, "experts": (jax.tree_util.tree_map(lambda a: a[None], lp["experts"]), 0)}
    want = ref.routed_experts(rlp, y.reshape(18, 32), ref.hyper(CFG))[0]
    np.testing.assert_allclose(np.asarray(whole).reshape(18, 32), np.asarray(want),
                               atol=EXACT)


# -- through latent pages and state rows --------------------------------------------------


LENS, NEW = [41, 26, 67], 5
ROWS = [np.random.RandomState(21 + r).randint(1, 128, n + NEW).astype(np.int32)
        for r, n in enumerate(LENS)]


@pytest.mark.parametrize("chunk,kern", [(8, {}), (66, {}), (20, INTERPRET)],
                         ids=["gather-8", "gather-66", "paged-20"])
def test_chunked_prefill_then_decode_matches_reference(params, exact, chunk, kern):
    """The logits of every step, and the state and the window each row left
    in its slot against the reference's after the positions it fed."""
    got, (kp, vp) = _through_the_cache(CFG, params, ROWS, LENS, NEW, chunk, kern)
    hp = ref.hyper(CFG)
    assert ref.linear_layers_ahead(hp) == 2
    for r, n in enumerate(LENS):
        want = _reference(params, ROWS[r])[n - 1:n + NEW - 1]
        np.testing.assert_allclose(got[r], want, atol=EXACT)
        fed = n + NEW - 1
        with jax.default_matmul_precision("highest"):
            _, _, states, windows, _ = ref.decoder_logits(
                params, jnp.asarray(ROWS[r]), 0, new=1, hp=hp, fed=fed)
        np.testing.assert_allclose(np.asarray(kp["kda"][:, r + 1]),
                                   np.asarray(states), atol=EXACT)
        np.testing.assert_allclose(np.asarray(vp["kda"][:, r + 1]),
                                   np.asarray(windows), atol=EXACT)
        held = ref.state_verdict(kp["kda"][:, r + 1], vp["kda"][:, r + 1],
                                 states, windows, 2)
        assert max(held["ahead"], *held["behind"]) < 1e-3 and held["bf16_share"] < 0.01


@KERNELS
def test_padding_and_idle_lanes_leave_a_state_alone(params, kern):
    """A chunk's padded positions and a decode step's idle lanes move neither
    state nor window: rows other than the step's own, the scratch row
    included, stay bit for bit."""
    kp, vp = init_page_pool(CFG, 11, PAGE, slots=3)
    kp = {**kp, "kda": jax.random.normal(jax.random.PRNGKey(1), kp["kda"].shape)}
    vp = {**vp, "kda": jax.random.normal(
        jax.random.PRNGKey(2), vp["kda"].shape).astype(jnp.bfloat16)}
    table = jnp.asarray([[3, 1, 5, 7]], jnp.int32)
    ids = np.zeros((1, 16), np.int32)
    ids[0, :5] = IDS[:5]
    args = (jnp.asarray([8]), jnp.asarray([5]), table)
    chunked = jax.jit(lambda p, *a: paged_prefill_chunk(
        p, CFG, *a, ssm_rows=jnp.asarray([2]), **kern))
    _, kp2, vp2, _ = chunked(params, jnp.asarray(ids), *args, kp, vp)
    for before, after in ((kp["kda"], kp2["kda"]), (vp["kda"], vp2["kda"])):
        np.testing.assert_array_equal(np.asarray(after[:, [0, 1, 3]], np.float32),
                                      np.asarray(before[:, [0, 1, 3]], np.float32))
        assert np.abs(np.asarray(after[:, 2], np.float32)
                      - np.asarray(before[:, 2], np.float32)).max() > 0
    # the same five tokens with no padding behind them leave the same row
    _, kp3, vp3, _ = chunked(params, jnp.asarray(ids[:, :5]), *args, kp, vp)
    np.testing.assert_allclose(np.asarray(kp3["kda"][:, 2]), np.asarray(kp2["kda"][:, 2]),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(vp3["kda"][:, 2], np.float32),
                                  np.asarray(vp2["kda"][:, 2], np.float32))
    # a decode step with slot 1 alone active
    tables = jnp.zeros((3, 4), jnp.int32).at[1].set(table[0])
    _, kp4, vp4, _ = jax.jit(lambda p, *a: paged_decode_step(p, CFG, *a, **kern))(
        params, jnp.asarray([0, 9, 0]), jnp.asarray([0, 13, 0]),
        jnp.asarray([False, True, False]), tables, kp2, vp2)
    for before, after in ((kp2["kda"], kp4["kda"]), (vp2["kda"], vp4["kda"])):
        np.testing.assert_array_equal(np.asarray(after[:, [0, 1, 3]], np.float32),
                                      np.asarray(before[:, [0, 1, 3]], np.float32))
        assert np.abs(np.asarray(after[:, 2], np.float32)
                      - np.asarray(before[:, 2], np.float32)).max() > 0


@KERNELS
def test_a_reused_slot_starts_from_zeros(params, exact, kern):
    """A first chunk (offset 0) reads a zero state and an empty window
    whatever its slot's row held: the second tenant's logits are those of a
    fresh pool."""
    kp, vp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    init_page_pool(CFG, 11, PAGE, slots=2))
    dirty_k = {**kp, "kda": jnp.full_like(kp["kda"], 7.0)}
    dirty_v = {**vp, "kda": jnp.full_like(vp["kda"], 5.0)}
    table = jnp.asarray([[3, 1, 5, 7]], jnp.int32)
    ids = jnp.asarray(IDS[None, :12])
    args = (jnp.asarray([0]), jnp.asarray([12]), table)
    chunked = jax.jit(lambda p, *a: paged_prefill_chunk(
        p, CFG, *a, ssm_rows=jnp.asarray([1]), **kern))
    clean, *_ = chunked(params, ids, *args, kp, vp)
    reused, kp2, vp2, _ = chunked(params, ids, *args, dirty_k, dirty_v)
    np.testing.assert_allclose(np.asarray(clean), np.asarray(reused), atol=1e-6)
    assert (np.asarray(kp2["kda"][:, 2]) == 7.0).all()        # the other slot's
    assert (np.asarray(vp2["kda"][:, 2]) == 5.0).all()
    with pytest.raises(ValueError, match="names its rows of the state pool"):
        paged_prefill_chunk(params, CFG, ids, *args, kp, vp)


# -- the server -----------------------------------------------------------------------


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


async def _serve(srv, prompts, new=6):
    return await asyncio.gather(*[srv.generate(p, new) for p in prompts])


def test_the_server_runs_ahead_and_reuses_slots():
    """Five prompts over three slots: slots own latent pages AND a row of the
    state pool; the model is stateful and, no EOS being live, runs one step
    ahead of the device; each request's tokens are those of a server it has
    to itself."""
    server = _proc()._server
    assert server._stateful and server._ahead and not server._fuses
    assert not pd.fusable(CFG)
    outs = asyncio.run(_serve(server, PROMPTS))
    alone = [asyncio.run(_serve(_proc()._server, [p]))[0] for p in PROMPTS[3:]]
    assert outs[3:] == alone and [len(o) for o in outs] == [6] * 5
    assert max(t[2] for t in server._state_tenant) >= 2       # a slot was reused
    assert server._steps_ahead > 0
    assert len(server._free_pages) == server.num_pages - 1
    st = server.slot_state(0)
    assert st["state"].shape == (4, 4, 16, 16) and st["state"].dtype == np.float32
    assert st["window"].shape == (4, 3, 192) and st["tenancy"] >= 1


def test_running_ahead_equals_lockstep():
    """``dispatch_depth: 1`` is the parity reference: the same tokens and the
    same state and window in every slot."""
    ahead, lock = _proc()._server, _proc(dispatch_depth=1)._server
    assert ahead._ahead and not lock._ahead
    outs = [asyncio.run(_serve(s, PROMPTS)) for s in (ahead, lock)]
    assert outs[0] == outs[1]
    for slot in range(3):
        a, b = ahead.slot_state(slot), lock.slot_state(slot)
        assert a["prompt"] == b["prompt"] and a["tokens"] == b["tokens"]
        np.testing.assert_array_equal(a["state"], b["state"])
        np.testing.assert_array_equal(np.asarray(a["window"], np.float32),
                                      np.asarray(b["window"], np.float32))


def test_a_live_eos_serves_in_lockstep():
    assert not _proc(eos_id=5)._server._ahead


def test_server_counters_and_gauges_equal_a_hand_count():
    """One prompt of 21 tokens (chunks of 8: 8 + 8 + 5) and 6 new tokens, a
    share of the experts held."""
    server = _proc({"experts_held": (2, 6)})._server
    names = ("arkflow_gen_moe_assignments_total", "arkflow_gen_ssm_tokens_total",
             "arkflow_gen_ssm_masked_total")
    before = {(n, k): _counter(n, kind=k).value
              for n in names for k in ("chunk", "decode")}
    resets = server.m_ssm_resets.value
    out = asyncio.run(server.generate(
        np.random.RandomState(1).randint(1, 128, 21).tolist(), 6))
    assert len(out) == 6
    d = {key: _counter(key[0], kind=key[1]).value - v for key, v in before.items()}
    # 5 expert layers (layer 0's MLP is dense), 3 choices a token
    assert d[names[0], "chunk"] == 21 * 3 * 5 and d[names[0], "decode"] == 5 * 3 * 5
    assert d[names[1], "chunk"] == 21 and d[names[2], "chunk"] == 3
    assert d[names[1], "decode"] == 5 and d[names[2], "decode"] == 5 * 2
    assert server.m_ssm_resets.value - resets == 1
    # the gauges read the spec: a page of latent rows over the 2 attention
    # layers, a slot's states and windows over the 4 linear layers
    assert [g[1] for g in server.m_kv_live] == ["pages", "slots"]
    assert [g[2] for g in server.m_kv_live] == [
        PAGE * 2 * (16 + 128) * 2, 4 * (4 * 16 * 16 * 4 + 3 * 192 * 2)]
    assert {m.labels["pool"] for m in global_registry().collect()
            if m.name == "arkflow_gen_kv_live_bytes"} >= {"latent", "kda"}
    assert global_registry().gauge(
        "arkflow_gen_kv_bytes_per_token",
        labels={"model": "decoder_lm"}).value == kv_bytes_per_token(CFG) == 576


def test_the_paged_server_passes_its_probe_and_counts_its_walk():
    """The build-time probe holds the latent walk (unrotated), the
    per-channel delta rule's two kernels and the expert product to their
    plain forms, kernel by kernel."""
    server = _proc(decode_kernel="paged", kernel_interpret=True)._server
    parity = server.kernel_parity
    assert parity["ok"] and parity["kernels"] == [
        "latent_attention_decode", "latent_attention_chunk", "kda_state_update",
        "kda_chunk_scan", "expert_product"]
    walked = _counter("arkflow_gen_attn_pages_walked_total", kind="decode").value
    out = asyncio.run(server.generate(PROMPTS[1], 4))
    assert len(out) == 4
    assert _counter("arkflow_gen_attn_pages_walked_total", kind="decode").value > walked


# -- what is served and what is still refused -----------------------------------------


REFUSED = ("mesh_tp", "prefix_cache", "speculation", "one_shot_prefill", "kv_push",
           "batch", "swap", "integrity", "fused_chunk", "run_ahead_eos")


@pytest.mark.parametrize("feature", FEATURES)
def test_the_union_of_the_two_rows_is_what_the_model_answers_with(feature):
    """``UNSERVED``: the model has the rows ``latent`` and ``kda`` and is
    refused what either is (the latent row's reason first, the table's
    order), in the table's own sentences; ``run_ahead`` is served."""
    why = unserved(CFG, feature)
    assert (why is not None) == (feature in REFUSED)
    if why is not None:
        rows = [pd.UNSERVED[r].get(feature) for r in ("latent", "kda")]
        first = next(r for r in rows if r is not None)
        assert why == first.format(hc=1, pools="latent, kda")


@pytest.mark.parametrize("extra,needle", [
    ({"mesh": {"tp": 2}}, "latent.*one chip"),
    ({"serving": "batch"}, "latent.*serving: continuous"),
    ({"prefill_chunk": 0}, "pools latent, kda.*prefill_chunk > 0"),
    ({"prefix_cache_pages": 8}, "prefix_cache_pages.*pools latent, kda"),
    ({"speculative_tokens": 2}, "speculative_tokens.*pools latent, kda"),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else None)
def test_the_model_refuses_what_is_not_served_with_it(extra, needle):
    with pytest.raises(ConfigError, match=needle):
        _proc(**extra)


def test_one_shot_prefill_and_kv_push_are_refused_by_the_pools(params):
    kp, vp = init_page_pool(CFG, 9, PAGE, slots=2)
    with pytest.raises(ConfigError, match="pools latent, kda.*prefills in chunks"):
        paged_prefill(params, CFG, jnp.zeros((1, 16), jnp.int32), jnp.asarray([9]),
                      jnp.zeros((1, 2), jnp.int32), kp, vp)
    proc = _proc()
    assert getattr(proc, "disagg", None) is None
    with pytest.raises(ConfigError, match="no wire format"):
        asyncio.run(proc._server.prefill_export([1, 2, 3], 2))


# -- the judge and the controls it refuses ---------------------------------------------


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


@pytest.mark.parametrize("control", ["no_dt_bias", "head_decay", "rotated_kr",
                                     "no_decay", "gate_is_silu"])
def test_judge_refuses_the_control_by_its_tokens(params, exact, monkeypatch, control):
    """Tokens the program serves under a control are not the reference's
    (float32 products here: the limits are held at a fiftieth)."""
    CONTROLS[control](monkeypatch)
    tokens = [_greedy(params, CFG, p, 8) for p in JUDGED]
    verdict = ref.judge_rows(params, ref.hyper(CFG), JUDGED, tokens, longest=96,
                             shares=0.02)
    assert not verdict["ok"] and verdict["unexplained"] > 0


#: heads of 16 round coarser than the cell's heads of 128 (0.0098 sound here,
#: 0.0056 to 0.0064 on the chip): the judge holds a rehearsal's states ahead
#: of the routers to twice the cell's limit, and so do these
TINY_STATE_REL_ERR = 2 * ref.STATE_REL_ERR


def _served_rows():
    """Rows through the served path (chunks, then decode through pages and
    state rows), judged with the states and windows they left."""
    proc = _proc(slots=1)
    server = proc._server
    for p in PROMPTS[:2]:
        asyncio.run(server.generate(p, 6))
    st = server.slot_state(0)
    return ref.judge_rows(
        proc.params, ref.hyper(proc.cfg), [list(st["prompt"])], [list(st["tokens"])],
        96, states=[st["state"]], windows=[st["window"]], shares=1e9)


@functools.lru_cache(maxsize=None)
def _sound_rows():
    return _served_rows()


@pytest.mark.parametrize("control,reads", [
    ("no_dt_bias", "state_rel_err"), ("head_decay", "state_rel_err"),
    ("bf16_state", "state_bf16_values_share")])
def test_the_state_a_row_left_sees_the_control(monkeypatch, control, reads):
    """Rule (d) through the served path: a sound server's state is the
    reference's to the bfloat16 products' rounding, and each control over
    the rule moves it past its limit (a state held in bfloat16 over a
    23-token prompt by its values' own bits: by distance it takes the chip's
    thousand-token rows, PERF.md section 6 PR 59)."""
    good = _sound_rows()
    assert good["ok"] and good["state_rel_err"] < TINY_STATE_REL_ERR
    assert good["state_bf16_values_share"] < ref.STATE_BF16_SHARE
    CONTROLS[control](monkeypatch)
    bad = _served_rows()
    assert not bad["ok"]
    limit = {"state_rel_err": TINY_STATE_REL_ERR,
             "state_bf16_values_share": ref.STATE_BF16_SHARE}[reads]
    assert bad[reads] > limit


def _probe():
    proc = _proc(slots=1)
    server = proc._server
    for p in PROMPTS[:2]:
        asyncio.run(server.generate(p, 3))
    return ref.reuse_probe(server, proc.params, ref.hyper(proc.cfg), 7, 128,
                           shares=2.0)


def test_the_reuse_probe_sees_a_state_that_survives(monkeypatch):
    """Rule (e): after two requests over one slot, a one-token prompt's
    chunk leaves an empty window before its own input and the state of that
    token alone; under the control the earlier tenant's are still there."""
    good = _probe()
    assert good["ok"] and good["tenancy"] == 3 and good["before_abs_max"] == 0.0
    assert good["state_rel_err"] < TINY_STATE_REL_ERR
    CONTROLS["state_survives"](monkeypatch)
    bad = _probe()
    assert not bad["ok"] and bad["before_abs_max"] > 0.0
    assert bad["state_rel_err"] > TINY_STATE_REL_ERR


def _latent_rows(kern):
    """Rule (f) through the served path: after two requests over the slots, a
    44-token prompt asking four tokens (six chunks of 8, the last padded;
    three decode steps), then the rows its pages hold against the forward's."""
    proc = _proc(slots=2, **kern)
    server = proc._server
    asyncio.run(_serve(server, PROMPTS[1:3], 3))
    cached = ref.latent_probe(server, 7, 128, 44)
    assert cached["latent"][0].shape == (2, 47, 16)
    assert len(cached["prompt"]) == 44 and len(cached["tokens"]) == 4
    st = server.slot_state(0)
    return ref.judge_rows(
        proc.params, ref.hyper(proc.cfg), [list(st["prompt"])], [list(st["tokens"])],
        96, states=[st["state"]], windows=[st["window"]], shares=1e9, probe=cached)


@KERNELS
def test_judge_holds_the_latent_rows_the_served_programs_wrote(monkeypatch, kern):
    """Rule (f): a sound server's latent pages hold the forward's rows to
    the bfloat16 products' rounding — the chunk program's and the decode
    program's alike — and with the shared key ROTATED (the control no token
    rule sees on the chip) every position's row is off by its own length."""
    good = _latent_rows(kern)
    assert good["latent_rows_held"] == 47
    assert good["latent_behind_key_abs_max"] == 0.0
    for rows in ("latent_rel_err", "latent_rel_err_decode_rows"):
        assert good[rows] < ref.LATENT_REL_ERR / 2, (rows, good[rows])
    CONTROLS["rotated_kr"](monkeypatch)
    bad = _latent_rows(kern)
    for rows in ("latent_rel_err", "latent_rel_err_decode_rows"):
        assert bad[rows] > 2 * ref.LATENT_REL_ERR, (rows, bad[rows])


def test_latent_verdict_reads_a_median_over_positions():
    """One position far off (a re-routed one) moves the largest distance and
    not the verdict; every position off does."""
    rng = np.random.RandomState(0)
    want = rng.randn(2, 40, 12).astype(np.float32)
    rows, keys = want[..., :8].copy(), np.pad(want[..., 8:], ((0, 0), (0, 0), (0, 4)))
    rows[1, 5] *= 2.0
    got = ref.latent_verdict((rows, keys), want, 3)
    assert got["latent_rel_err"] == 0.0 and got["latent_rel_err_decode_rows"] == 0.0
    assert got["latent_rel_err_largest"] == pytest.approx(1.0)
    assert got["latent_behind_key_abs_max"] == 0.0 and got["latent_rows_held"] == 40
    keys[..., :4] = -keys[..., :4]
    keys[..., 5] = 1.0
    got = ref.latent_verdict((rows, keys), want, 3)
    assert got["latent_rel_err"] == pytest.approx(2.0)
    assert got["latent_rel_err_decode_rows"] == pytest.approx(2.0)
    assert got["latent_behind_key_abs_max"] == 1.0


def test_judge_holds_the_float32_leaves():
    masters = dec.init(jax.random.PRNGKey(3), CFG)
    placed = jax.tree_util.tree_map(lambda leaf, dt: leaf.astype(dt), masters,
                                    dec.serve_dtypes(CFG))
    assert ref.stated_float32_leaves_differ(placed, masters) == 0
    for leaf, n in (("kda_A_log", 3 * 4), ("kda_dt_bias", 3 * 64),
                    ("router_bias", 3 * 16)):
        was = placed["kda_layers"][leaf]
        placed["kda_layers"][leaf] = was.astype(jnp.bfloat16)
        assert ref.stated_float32_leaves_differ(placed, masters) == n
        placed["kda_layers"][leaf] = was


# -- what this model's arrival must leave alone ------------------------------------------

#: sha256 (first 16 hex) of the jaxprs (source positions stripped) of a decode
#: step and a prefill chunk of the Gated DeltaNet layout (``test_gdn_gqa_moe.
#: TINY``: ``qwen3next_l8``'s), recorded at PR 59's parent (9bd2225): the seam
#: both linear mixers are called through (``decoder.linear_mixer``) and the
#: probe's shared lines trace the same program for the mixer that was there.
#: The latent layouts' (``kanana2``, ``dots3``, ``xing4``) are
#: ``test_fused_step.PARENT_GOLDEN``'s, which holds as it was
#: PR 60 RE-RECORDED the two ``paged`` entries: it changed the
#: kernel's body on purpose (both products take the type the pools hold; a
#: chunk tile reads a head's rows out of the slot's own words), so every
#: program that holds ``_paged_kernel`` moved and nothing else did (the layout's
#: full-attention layers call it; the ``gather`` two stand as recorded)
#: PR 61 RE-RECORDED the two ``paged`` entries (2711701e5e0aa4e2, 8ca449a2ed3963f3 before it): its walk is ``_page_walk``'s (a run of ``PAGE_RUN`` neighbours a copy out of pools that
#: ride as flat rows, a program's last step starting the next program's first
#: group): every program that holds ``_paged_kernel`` moved — a window call's
#: too, whose walk takes no runs but shares the copies and the hand-on — and
#: nothing else did
GDN_PARENT_GOLDEN = {
    "decode.paged": "5afaf368bd21de59", "chunk.paged": "357ca1f4e65014b2",
    "decode.gather": "05d2f806133264e8", "chunk.gather": "f45e7c39e0e07910"}


@pytest.mark.parametrize("case", sorted(GDN_PARENT_GOLDEN))
def test_the_gated_delta_net_s_programs_are_the_parent_s(case):
    import hashlib
    import re

    from tests.test_gdn_gqa_moe import TINY as GDN

    step, kern = case.split(".")
    cfg = dec.DecoderConfig(**GDN)
    p = jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg))
    kp, vp = jax.eval_shape(lambda: init_page_pool(cfg, 9, 8, 0, slots=2))
    kw = dict(attention_kernel=kern, kernel_interpret=False)
    i32 = jnp.int32
    jaxpr = jax.make_jaxpr({
        "decode": lambda p, k, v: paged_decode_step(
            p, cfg, jnp.zeros((2,), i32), jnp.ones((2,), i32), jnp.ones((2,), bool),
            jnp.zeros((2, 4), i32), k, v, **kw),
        "chunk": lambda p, k, v: paged_prefill_chunk(
            p, cfg, jnp.zeros((1, 8), i32), jnp.zeros((1,), i32),
            jnp.full((1,), 5, i32), jnp.zeros((1, 4), i32), k, v,
            ssm_rows=jnp.ones((1,), i32), **kw)}[step])(p, kp, vp)
    text = re.sub(r"0x[0-9a-f]+", "0x", re.sub(r" at [^\s\]]+:\d+", "", str(jaxpr)))
    got = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert got == GDN_PARENT_GOLDEN[case], got


# -- a checkpoint's key names, the cell's files ------------------------------------------


def _kimi_state_dict(params, cfg) -> dict:
    """``params`` as a ``KimiLinearForCausalLM`` state dict: numpy arrays
    under the published key names, linear weights [out, in], a conv1d's
    [channels, 1, taps], q / k / v as three matrices and three convs."""
    n = lambda a: np.asarray(a, np.float32)  # noqa: E731
    state = {"model.embed_tokens.weight": n(params["embed"]["table"]),
             "model.norm.weight": n(params["norm_out"]["scale"]),
             "lm_head.weight": n(params["lm_head"]["w"]).T}
    p3 = cfg.kda_heads * cfg.kda_head_dim
    at = 0
    for name, first, stop, kind, routed, _ in dec.layer_runs(cfg):
        for j in range(first, stop):
            lp = jax.tree_util.tree_map(lambda a: a[j], params[name])
            p, a = f"model.layers.{at}", f"model.layers.{at}.self_attn"
            at += 1
            state[f"{p}.input_layernorm.weight"] = n(lp["attn_norm"]["scale"])
            state[f"{p}.post_attention_layernorm.weight"] = n(lp["mlp_norm"]["scale"])
            if kind == LINEAR:
                for i, x in enumerate("qkv"):
                    cols = slice(i * p3, (i + 1) * p3)
                    state[f"{a}.{x}_proj.weight"] = n(lp["kda_qkv"]["w"])[:, cols].T
                    state[f"{a}.{x}_conv1d.weight"] = n(lp["kda_conv_w"])[cols, None]
                for ours, theirs in (("kda_fa", "f_a_proj"), ("kda_fb", "f_b_proj"),
                                     ("kda_b", "b_proj"), ("kda_ga", "g_a_proj"),
                                     ("kda_gb", "g_b_proj"), ("kda_out", "o_proj")):
                    state[f"{a}.{theirs}.weight"] = n(lp[ours]["w"]).T
                state[f"{a}.A_log"] = n(lp["kda_A_log"]).reshape(1, 1, -1, 1)
                state[f"{a}.dt_bias"] = n(lp["kda_dt_bias"])
                state[f"{a}.o_norm.weight"] = n(lp["kda_norm"]["scale"])
            else:
                for ours, theirs in (("wq", "q_proj"), ("wkv_a", "kv_a_proj_with_mqa"),
                                     ("wkv_b", "kv_b_proj"), ("wo", "o_proj")):
                    state[f"{a}.{theirs}.weight"] = n(lp[ours]["w"]).T
                state[f"{a}.kv_a_layernorm.weight"] = n(lp["kv_norm"]["scale"])
            if not routed:
                for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                     ("w_down", "down_proj")):
                    state[f"{p}.mlp.{theirs}.weight"] = n(lp[ours]["w"]).T
                continue
            m = f"{p}.block_sparse_moe"
            state[f"{m}.gate.weight"] = n(lp["router"]["w"]).T
            state[f"{m}.gate.e_score_correction_bias"] = n(lp["router_bias"])
            for ours, theirs, shared in (("w_gate", "w1", "gate_proj"),
                                         ("w_up", "w3", "up_proj"),
                                         ("w_down", "w2", "down_proj")):
                w = n(lp["experts"][ours])
                for e in range(cfg.n_routed_experts):
                    state[f"{m}.experts.{e}.{theirs}.weight"] = w[e].T
                state[f"{m}.shared_experts.{shared}.weight"] = w[-1].T
    return state


def test_a_state_dict_of_the_published_names_fills_the_tree():
    """``from_hf_state_dict`` on a seeded state dict of the published key
    names (torch's [out, in], a conv1d's [channels, 1, taps]): the program's
    tree, leaf for leaf, and the same logits."""
    small = dataclasses.replace(CFG, n_routed_experts=4, num_experts_per_tok=2)
    want = dec.init(jax.random.PRNGKey(11), small)
    state = _kimi_state_dict(want, small)
    assert "model.layers.0.self_attn.q_conv1d.weight" in state
    assert state["model.layers.0.self_attn.q_conv1d.weight"].shape == (64, 1, 4)
    assert "model.layers.2.self_attn.kv_a_proj_with_mqa.weight" in state
    assert "model.layers.1.block_sparse_moe.gate.e_score_correction_bias" in state
    got = dec.from_hf_state_dict(state, small)
    flat_w, tree_w = jax.tree_util.tree_flatten(want)
    flat_g, tree_g = jax.tree_util.tree_flatten(got)
    assert tree_w == tree_g
    for a, b in zip(flat_w, flat_g):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_cell_s_files_agree():
    """The configuration file carries every published key of the catalog's
    entry, cut only where ``reduced`` says, and builds the program's config."""
    with open(ROOT / "benchmark/configs/kimi-linear-48b-a3b-l8-ep8.json") as f:
        c = json.load(f)
    published = {"first_k_dense_replace": 1, "head_dim": 72, "hidden_size": 2304,
                 "intermediate_size": 9216, "kv_lora_rank": 512,
                 "mla_use_nope": True, "model_max_length": 1048576,
                 "moe_intermediate_size": 1024, "moe_renormalize": True,
                 "num_attention_heads": 32, "num_expert_group": 1,
                 "num_experts_per_token": 8, "num_key_value_heads": 32,
                 "num_shared_experts": 1, "q_lora_rank": None,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "rms_norm_eps": 1e-05, "rope_theta": 10000,
                 "routed_scaling_factor": 2.446, "topk_group": 1, "v_head_dim": 128}
    assert {k: c[k] for k in published} == published
    assert c["linear_attn_config"]["num_heads"] == 32
    assert c["linear_attn_config"]["head_dim"] == 128
    assert c["linear_attn_config"]["short_conv_kernel_size"] == 4
    assert len(c["linear_attn_config"]["kda_layers"]) == 20
    assert c["linear_attn_config"]["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (8, 32, 20480)
    assert c["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                              "vocab_size": 163840}
    assert c["layer_types"] == [
        LINEAR if i + 1 in c["linear_attn_config"]["kda_layers"] else FULL
        for i in range(8)]
    cfg = dec.DecoderConfig(**{ours: c[theirs]
                               for ours, theirs in c["model_config_from"].items()})
    assert cfg.kda and cfg.held == (0, 32) and cfg.n_routed_experts == 256
    assert [p.name for p in cache_spec(cfg)] == ["latent", "kda"]
    assert [p.bytes_per_slot for p in cache_spec(cfg)] == [0, 13_025_280]
    assert kv_bytes_per_token(cfg) == 2560 and not cfg.attn(FULL).rotate
    assert cfg.dense_layers == 1 and cfg.expert_layers == 7
    with open(ROOT / "benchmark/traffic/diagnose_backlog.json") as f:
        t = json.load(f)
    assert t["lengths"] == {"dist": "lognormal", "median": 1024, "sigma": 1.0,
                            "min": 128, "max": 16384}
    assert (t["batch_rows"], t["pool_rows"], t["stratify"], t["fill_rows"],
            t["settle_s"], t["order"]) == (4, 256, 4, 16, 0.5, "fixed")
    proc = c["engine"]["streams"][0]["pipeline"]["processors"][0]
    assert (proc["slots"], proc["max_input"], proc["max_new_tokens"],
            proc["prefill_chunk"], proc["eos_id"]) == (128, 16384, 768, 512, -1)
