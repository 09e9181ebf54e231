"""Gated DeltaNet layers among gated grouped-query attention layers, softmax-
routed experts of which a share is held and a sigmoid-gated shared expert
(the Qwen3-Next-80B-A3B layout), through the paged serving path, held to the
plain reference ``benchmark/references/gdn_gqa_moe.py`` on seeded weights at
tiny widths: five layers L L F L F, two K/V heads of 256 (the pools hold a
head a layer) and, ``h128``, of 128, value heads of 128 lanes, pages of 8,
a float32 state and a three-row window a slot (Pallas in interpret mode).

The equations are held EXACTLY: with the program's products switched to
float32 (``exact``) its logits are the reference's to 2e-4 through the full
forward and through chunked prefill and decode over pages and state rows,
choices included. The delta rule's chunked form is held to the recurrence
token by token, each kernel to its plain form, and the served program to the
reference's judge, which refuses each control the builder runs on the chip
(``CONTROLS``).
"""

from __future__ import annotations

import asyncio
import dataclasses
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
from arkflow_tpu.models import paged_decode as pd
from arkflow_tpu.models.paged_decode import (cache_spec, gqa_kernel_probe,
                                             init_page_pool, kv_bytes_per_token,
                                             paged_decode_step, paged_prefill,
                                             paged_prefill_chunk)
from arkflow_tpu.obs import global_registry
from arkflow_tpu.ops import gdn_scan as gs

ensure_plugins_loaded()


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/references/gdn_gqa_moe.py", "ref_gdn_gqa_moe")

FULL, LINEAR = dec.FULL, dec.LINEAR
KINDS = (LINEAR, LINEAR, FULL, LINEAR, FULL, LINEAR)
TINY = dict(vocab_size=128, dim=32, layers=5, heads=4, kv_heads=2, head_dim=256,
            ffn=64, max_seq=256, rope_theta=1e7, norm_eps=1e-6, qk_norm=True,
            partial_rotary_factor=0.25, attention_gate_type="elementwise",
            norm_unit_offset=True, layer_types=KINDS, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=128, linear_conv_kernel_dim=4,
            n_routed_experts=16, num_experts_per_tok=3, n_shared_experts=1,
            shared_expert_gate=True, moe_intermediate_size=16,
            first_k_dense_replace=0, scoring_func="softmax", topk_method="greedy")
CFG = dec.DecoderConfig(**TINY)
#: heads of 128 lanes: pools [.., 2, 128], one call of the kernel a layer
H128 = dataclasses.replace(CFG, head_dim=128)
#: this chip's share of an 8-way expert-parallel deployment
HELD = dataclasses.replace(CFG, experts_held=(2, 2))
CONFIGS = {"h256": CFG, "h128": H128}
PAGE = 8
INTERPRET = dict(attention_kernel="paged", kernel_interpret=True)
KERNELS = pytest.mark.parametrize("kern", [{}, INTERPRET], ids=["gather", "paged"])


def _params(cfg):
    """Seeded weights as placed."""
    return jax.tree_util.tree_map(
        lambda leaf, dt: leaf.astype(dt).astype(jnp.float32),
        dec.init(jax.random.PRNGKey(3), cfg), dec.serve_dtypes(cfg))


@pytest.fixture(scope="module")
def all_params():
    return {name: _params(cfg) for name, cfg in CONFIGS.items()}


@pytest.fixture(scope="module")
def params(all_params):
    return all_params["h256"]


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


# -- the fourth kind: config, cache spec, layer runs ---------------------------------


def test_linear_attention_is_a_kind_of_layer_with_no_attention_weights():
    assert CFG.linear and CFG.stateful and CFG.by_runs and CFG.kind_stacks
    assert CFG.out_gate and CFG.hetero and not (CFG.hybrid or CFG.conv or CFG.layered)
    assert CFG.dense_layers == 0 and CFG.expert_layers == 5
    assert CFG.attn_kinds == (FULL, FULL) and CFG.kinds == KINDS[:5]
    assert CFG.gdn_conv_dim == 2 * 2 * 16 + 4 * 128
    assert dec.layer_runs(CFG) == [
        ("gdn_layers", 0, 2, LINEAR, True, 0), ("layers", 0, 1, FULL, True, 0),
        ("gdn_layers", 2, 3, LINEAR, True, 2), ("layers", 1, 2, FULL, True, 1)]
    masters = dec.init(jax.random.PRNGKey(3), CFG)
    assert set(masters) == {"embed", "norm_out", "lm_head", "gdn_layers", "layers"}
    gdn, full = masters["gdn_layers"], masters["layers"]
    assert gdn["gdn_in"]["w"].shape == (3, 32, 64 + 512 + 512)  # q | k | v | z
    assert gdn["gdn_ba"]["w"].shape == (3, 32, 8)
    assert gdn["gdn_conv_w"].shape == (3, 576, 4) and gdn["gdn_A_log"].shape == (3, 4)
    assert gdn["gdn_norm"]["scale"].shape == (3, 128)          # one set for all heads
    assert gdn["gdn_out"]["w"].shape == (3, 512, 32)
    assert "wq" not in gdn and "router_bias" not in gdn         # greedy: no bias
    assert full["wq"]["w"].shape == full["w_out_gate"]["w"].shape == (2, 32, 1024)
    assert full["shared_gate"]["w"].shape == (2, 32, 1)
    assert full["experts"]["w_gate"].shape == (2, 16 + 1, 32, 16)
    # norm scales are offsets from one, seeded small and non-zero; the
    # mixer's gated norm keeps a plain scale around one
    for stack, norm in (("gdn_layers", "attn_norm"), ("layers", "q_head_norm"),
                        ("layers", "mlp_norm")):
        scale = np.asarray(masters[stack][norm]["scale"])
        assert 0 < np.abs(scale).max() < 0.6 and abs(scale.mean()) < 0.1
    assert abs(float(np.asarray(masters["norm_out"]["scale"]).mean())) < 0.1
    assert abs(float(np.asarray(gdn["gdn_norm"]["scale"]).mean()) - 1) < 0.1
    dtypes = dec.serve_dtypes(CFG)
    assert jax.tree_util.tree_structure(dtypes) == jax.tree_util.tree_structure(masters)
    f32 = {"gdn_A_log", "gdn_dt_bias", "gdn_norm", "router", "shared_gate",
           "attn_norm", "mlp_norm", "q_head_norm", "k_head_norm", "norm_out"}
    for path, dt in jax.tree_util.tree_flatten_with_path(dtypes)[0]:
        keys = {str(k.key) for k in path}
        assert (dt == jnp.float32) == bool(keys & f32), keys


def test_cache_spec_states_a_fourth_per_slot_pool():
    kv, gdn = cache_spec(CFG)
    assert (kv.name, kv.layers, kv.widths, kv.split_heads) == (
        "kv", 2, (512, 512), True)
    assert (gdn.name, gdn.layers, gdn.per_slot, gdn.itemsizes) == (
        "gdn", 3, True, (4, 2))
    assert gdn.widths == (4 * 16 * 128, 3 * 576)
    assert gdn.bytes_per_slot == 3 * (4 * 16 * 128 * 4 + 3 * 576 * 2)
    assert kv_bytes_per_token(CFG) == 2 * 2 * 256 * 2 * 2 == 4096
    (kp, vp) = init_page_pool(CFG, 9, PAGE, slots=3)
    assert kp["kv"].shape == vp["kv"].shape == (2 * 2, 9, PAGE, 1, 256)  # a head a layer
    assert kp["gdn"].shape == (3, 4, 4, 16, 128) and kp["gdn"].dtype == jnp.float32
    assert vp["gdn"].shape == (3, 4, 3, 576) and vp["gdn"].dtype == jnp.bfloat16
    # a head of 128 lanes keeps the pools every per-head model has
    assert [p.split_heads for p in cache_spec(H128)] == [False, False]
    assert init_page_pool(H128, 9, PAGE, slots=3)[0]["kv"].shape == (2, 9, PAGE, 2, 128)
    # the published sizes: 4,096 B a token, 12.88 MB a slot
    big = dec.DecoderConfig(
        vocab_size=18992, dim=2048, layers=8, heads=16, kv_heads=2, head_dim=256,
        layer_types=((LINEAR,) * 3 + (FULL,)) * 2, linear_num_key_heads=16,
        linear_num_value_heads=32, linear_key_head_dim=128,
        linear_value_head_dim=128, linear_conv_kernel_dim=4, rope_interleave=False)
    assert kv_bytes_per_token(big) == 4096
    assert cache_spec(big)[1].bytes_per_slot == 6 * (2_097_152 + 49_152)


@pytest.mark.parametrize("sizes,needle", [
    (dict(layer_types=(LINEAR,) * 5), "among full_attention per-head K/V layers"),
    (dict(layer_types=(LINEAR, "sliding_attention", FULL, LINEAR, FULL),
          sliding_window=8), "pool gdn.*kv_window"),
    (dict(layer_types=(LINEAR, "conv", FULL, LINEAR, FULL), conv_L_cache=3),
     "pool gdn.*pool conv"),
    (dict(linear_num_value_heads=3), "linear_num_key_heads dividing"),
    (dict(layer_types=None), "without a linear_attention layer"),
    (dict(scoring_func="softmax", topk_method="noaux_tc"), "softmax with greedy"),
    (dict(shared_expert_gate=True, n_shared_experts=0), "shared_expert_gate weighs"),
], ids=["no-full", "window", "conv", "heads", "no-kind", "router", "gate"])
def test_config_refuses_by_name(sizes, needle):
    with pytest.raises(ConfigError, match=needle):
        dec.DecoderConfig(**{**TINY, **sizes})


def test_a_latent_model_still_needs_a_leading_dense_layer():
    latent = dict(vocab_size=64, dim=32, layers=2, heads=2, kv_lora_rank=16,
                  qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
                  rope_interleave=True, n_routed_experts=4, num_experts_per_tok=2,
                  moe_intermediate_size=8)
    with pytest.raises(ConfigError, match="a latent model has at least one"):
        dec.DecoderConfig(**latent, first_k_dense_replace=0)
    assert dec.DecoderConfig(**latent, first_k_dense_replace=1).dense_layers == 1


# -- the delta rule -------------------------------------------------------------------


def _operands(b, t, h, dk, dv, seed, strength=1.0):
    """Operands of the statistics the layer hands the rule: unit keys, scaled
    unit queries, gates of every strength."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -jnp.exp(strength * jax.random.normal(ks[3], (b, t, h)) - 2.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return jax.random.normal(ks[5], (b, h, dk, dv)), (q, k, v, g, beta)


def test_the_delta_rule_by_hand_on_three_tokens():
    """One head, keys of 2, values of 1: the state is a column of two."""
    k = jnp.asarray([[1.0, 0.0], [0.0, 1.0], [0.6, 0.8]]).reshape(1, 3, 1, 2)
    q = jnp.asarray([[1.0, 1.0], [1.0, 0.0], [0.0, 2.0]]).reshape(1, 3, 1, 2)
    v = jnp.asarray([2.0, 4.0, 1.0]).reshape(1, 3, 1, 1)
    g = jnp.log(jnp.asarray([1.0, 0.5, 0.5])).reshape(1, 3, 1)
    beta = jnp.asarray([0.5, 1.0, 0.5]).reshape(1, 3, 1)
    # t0: S = [0, 0]; u = 0; d = 0.5 (2 - 0) = 1; S = [1, 0]; o = 1
    # t1: S = [0.5, 0]; u = 0; d = 4; S = [0.5, 4]; o = 0.5
    # t2: S = [0.25, 2]; u = 0.15 + 1.6 = 1.75; d = 0.5 (1 - 1.75) = -0.375
    #     S = [0.25 - 0.225, 2 - 0.3] = [0.025, 1.7]; o = 3.4
    for fn in (gs.recurrent_from, gs.chunk_from):
        o, s = fn(jnp.zeros((1, 1, 2, 1)), q, k, v, g, beta)
        np.testing.assert_allclose(np.asarray(o).ravel(), [1.0, 0.5, 3.4], atol=1e-6)
        np.testing.assert_allclose(np.asarray(s).ravel(), [0.025, 1.7], atol=1e-6)


@pytest.mark.parametrize("t,strength", [(64, 1.0), (128, 1.0), (150, 1.0),
                                        (200, 2.5), (5, 1.0), (70, 0.0)],
                         ids=["block", "two", "ragged", "strong", "short", "weak"])
def test_the_chunked_form_is_the_recurrence(t, strength):
    """Blocks of 64, a ragged tail, gates near 0 and strongly negative."""
    s0, ops = _operands(2, t, 3, 16, 32, seed=t, strength=strength)
    if not strength:  # g within 1e-3 of 0: nothing is forgotten
        ops = (*ops[:3], ops[3] * 1e-2, ops[4])
    o1, s1 = gs.recurrent_from(s0, *ops)
    o2, s2 = gs.chunk_from(s0, *ops)
    np.testing.assert_allclose(np.asarray(o2), np.asarray(o1), atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s1), atol=2e-5)


def test_no_gate_and_no_step_leave_a_state_alone():
    s0, (q, k, v, g, beta) = _operands(2, 70, 3, 16, 32, seed=1)
    still = jnp.zeros_like(g)
    for fn in (gs.recurrent_from, gs.chunk_from):
        _, s = fn(s0, q, k, v, still, still)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s0))


@pytest.mark.parametrize("t", [1, 7, 130, 256])
def test_each_kernel_is_its_plain_form(t):
    """The pool's rows and layer ride in the block index: row 0 and the
    other layer stay as they were."""
    pool = jax.random.normal(jax.random.PRNGKey(9), (2, 5, 4, 16, 128))
    _, ops = _operands(3, t, 4, 16, 128, seed=t + 1)
    rows, fresh = jnp.asarray([2, 0, 4]), jnp.asarray([True, False, False])
    kern = dict(kernel=True, interpret=True)
    if t == 1:
        one = tuple(a[:, 0] for a in ops)
        want, got = (gs.gdn_state_update(pool, 1, rows, *one, **kw)
                     for kw in ({}, kern))
    else:
        want, got = (gs.gdn_chunk_scan(pool, 1, rows, fresh, *ops, **kw)
                     for kw in ({}, kern))
    for a, b in zip(want, got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(got[1][0]), np.asarray(pool[0]))
    np.testing.assert_array_equal(np.asarray(got[1][1, [1, 3]]),
                                  np.asarray(pool[1, [1, 3]]))


# -- the forward ----------------------------------------------------------------------


@pytest.mark.parametrize("name", list(CONFIGS))
def test_forward_matches_reference(all_params, exact, name):
    cfg, ids = CONFIGS[name], IDS[:70]
    got = np.asarray(dec.forward(all_params[name], cfg, jnp.asarray(ids[None])))[0]
    np.testing.assert_allclose(got, _reference(all_params[name], ids, cfg), atol=EXACT)


def _plain_scale(monkeypatch):
    """``w`` for ``1 + w``: the norms' scales as they are."""
    def plain(p, x, cfg):
        return cm.rms_norm(p, x, cfg.norm_eps)
    for mod in (dec, pd):
        _set(monkeypatch, mod, "_norm", plain)


def _no_dt_bias(monkeypatch):
    real = dec.gdn_operands

    def without(lp, *args, **kw):
        return real({**lp, "gdn_dt_bias": jnp.zeros_like(lp["gdn_dt_bias"])},
                    *args, **kw)
    _set(monkeypatch, dec, "gdn_operands", without)  # (read through dec.linear_mixer)


def _set(monkeypatch, target, name, value):
    (monkeypatch.setattr if monkeypatch is not None else setattr)(target, name, value)


def _operands_control(change):
    """A control over what the delta rule reads: ``change(q, k, v, g, beta)``."""
    def apply(monkeypatch=None):
        real = dec.gdn_operands

        def changed(lp, conved, b, a, cfg, valid=None):
            return change(*real(lp, conved, b, a, cfg, valid), conved=conved,
                          cfg=cfg)
        _set(monkeypatch, dec, "gdn_operands", changed)  # (read through dec.linear_mixer)
    return apply


def _raw_heads(x, heads, cfg):
    """Conv outputs as heads WITHOUT the L2 norm, a value head each."""
    x = x.reshape(*x.shape[:2], cfg.linear_num_key_heads, cfg.linear_key_head_dim)
    return jnp.repeat(x, heads // cfg.linear_num_key_heads, axis=2)


def _not_normalised(q, k, v, g, beta, conved, cfg):
    nk, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    nv = cfg.linear_num_value_heads
    return (_raw_heads(conved[..., :nk * dk], nv, cfg) * dk ** -0.5,
            _raw_heads(conved[..., nk * dk:2 * nk * dk], nv, cfg), v, g, beta)


_no_qk_l2 = _operands_control(_not_normalised)
#: ``exp(g)`` left out: the gates' step still tells padding (beta), the state
#: never decays
_no_decay = _operands_control(lambda q, k, v, g, beta, **_: (q, k, v, g * 0, beta))


def _no_delta(monkeypatch=None):
    """``d = beta v``: the state's own answer ``u`` is not taken off."""
    def rule(s0, q, k, v, g, beta):
        f32 = jnp.float32

        def step(s, xs):
            q_t, k_t, v_t, g_t, b_t = xs
            s = jnp.exp(g_t)[..., None, None] * s
            s = s + k_t[..., :, None] * (b_t[..., None] * v_t)[..., None, :]
            return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

        s_t, o = jax.lax.scan(step, s0.astype(f32), tuple(
            jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta)))
        return jnp.moveaxis(o, 0, 1), s_t

    _set(monkeypatch, gs, "recurrent_from", rule)
    _set(monkeypatch, gs, "chunk_from", rule)
    # the plain forms serve, whatever the server asked for
    for name in ("gdn_state_update", "gdn_chunk_scan"):
        real = getattr(gs, name)
        _set(monkeypatch, gs, name, lambda *a, _real=real, **kw: _real(*a))


def _gate_left_out(name):
    """One of the three sigmoid / SiLU gates left out (its factor 1)."""
    def apply(monkeypatch=None):
        if name == "output":       # the mixer's silu(z)
            real = dec.gdn_output

            def ungated(lp, o, z, cfg, dtype):
                # silu(z0) = 1 at z0 = 1.27846
                return real(lp, o, jnp.full_like(z, 1.2784645), cfg, dtype)
            _set(monkeypatch, dec, "gdn_output", ungated)  # (read through dec.linear_mixer)
        elif name == "attention":  # sigmoid(gate) on the attention's output
            for mod in (dec, pd):
                _set(monkeypatch, mod, "attn_out_gate", lambda lp, y, attn, cfg: attn)
        else:                      # sigmoid(y w_sg) on the shared expert
            real = dec.route_topk

            def ungated(lp, y, cfg, token_mask=None):
                return real(lp, y, dataclasses.replace(cfg, shared_expert_gate=False),
                            token_mask)
            _set(monkeypatch, dec, "route_topk", ungated)
    return apply


def _conv_row_late(monkeypatch=None):
    """The conv reads its inputs a row late: tap j on the input at t - 4 + j."""
    real = dec.gdn_conv

    def late(lp, ext, s):
        shifted = {**lp, "gdn_conv_w": jnp.concatenate(
            [lp["gdn_conv_w"][..., 1:], jnp.zeros_like(lp["gdn_conv_w"][..., :1])], -1)}
        return real(shifted, ext, s)
    _set(monkeypatch, dec, "gdn_conv", late)  # (read through dec.linear_mixer)


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


def _bf16_state(monkeypatch=None):
    """The state rounded to bfloat16 at every write (a pool held in
    bfloat16, whatever its declared type)."""
    update, scan = gs.gdn_state_update, gs.gdn_chunk_scan

    def rounded(fn):
        def call(state, *args, **kw):
            o, state = fn(state, *args, **kw)
            # (a cast to bfloat16 and back is dropped by the chip's compiler)
            return o, jax.lax.reduce_precision(state, exponent_bits=8,
                                               mantissa_bits=7)
        return call

    _set(monkeypatch, gs, "gdn_state_update", rounded(update))
    _set(monkeypatch, gs, "gdn_chunk_scan", rounded(scan))


def _state_survives(monkeypatch=None):
    """A prompt's first chunk does not reset its slot's state and window."""
    real = pd._gdn_paged

    def kept(lp, y, cfg, states, windows, layer, rows, fresh, valid, *kern):
        if fresh is not None:  # (None is a decode step)
            fresh = jnp.zeros_like(fresh)
        return real(lp, y, cfg, states, windows, layer, rows, fresh, valid, *kern)

    _set(monkeypatch, pd, "_gdn_paged", kept)


#: the controls the builder runs on the chip through the timed path (a driver
#: applies one, then runs the benchmark's cell): each must be REFUSED
CONTROLS = {"mantissa3": _mantissa3, "bf16_state": _bf16_state,
            "no_qk_l2": _no_qk_l2, "no_decay": _no_decay, "no_delta": _no_delta,
            "no_output_gate": _gate_left_out("output"),
            "no_attention_gate": _gate_left_out("attention"),
            "no_shared_gate": _gate_left_out("shared"),
            "plain_norm_scale": _plain_scale, "state_survives": _state_survives,
            "conv_row_late": _conv_row_late, "no_dt_bias": _no_dt_bias}


@pytest.mark.parametrize("ablation", [
    "no_qk_l2", "no_decay", "no_delta", "no_output_gate", "no_attention_gate",
    "no_shared_gate", "plain_norm_scale", "conv_row_late", "no_dt_bias"])
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
    hp = ref.hyper(CFG)
    yr = y.reshape(18, 32)
    rlp = {**lp, "experts": (jax.tree_util.tree_map(lambda a: a[None], lp["experts"]), 0)}
    want = ref.routed_experts(rlp, yr, hp)[0]
    np.testing.assert_allclose(np.asarray(whole).reshape(18, 32), np.asarray(want),
                               atol=EXACT)


def test_router_is_a_softmax_with_no_bias_and_the_gate_a_sigmoid(params, exact):
    lp = jax.tree_util.tree_map(lambda a: a[1], params["layers"])
    y = jax.random.normal(jax.random.PRNGKey(4), (6, 32), jnp.float32)
    cw, load = dec.route_topk(lp, y, CFG)
    p = jax.nn.softmax(y @ lp["router"]["w"], axis=-1)
    top, idx = jax.lax.top_k(p, 3)
    want = np.zeros((6, 16), np.float32)
    np.put_along_axis(want, np.asarray(idx), np.asarray(top / top.sum(-1, keepdims=True)), 1)
    np.testing.assert_allclose(np.asarray(cw[:, :16]), want, atol=1e-6)
    np.testing.assert_allclose(np.asarray(cw[:, 16]), np.asarray(
        jax.nn.sigmoid(y @ lp["shared_gate"]["w"]))[:, 0], atol=1e-6)
    assert int(load.sum()) == 18
    held, _ = dec.route_topk(lp, y, HELD)       # normalised over all the chosen
    np.testing.assert_allclose(np.asarray(held[:, :2]), want[:, 2:4], atol=1e-6)


# -- through pages and state rows ---------------------------------------------------


def _through_the_cache(cfg, params, rows, lens, new, chunk, kern, pages_per=18,
                       dtype=jnp.float32):
    """Chunked prefill of three ragged rows (row r in slot r), then lockstep
    decode steps fed the rows' own tokens: every step's logits, a row at a
    time, and the pools at the end."""
    kept = jnp.asarray(np.random.RandomState(2).permutation(
        np.arange(1, 1 + 3 * pages_per)).reshape(3, pages_per), jnp.int32)
    kp, vp = jax.tree_util.tree_map(
        lambda a: a.astype(dtype), init_page_pool(cfg, 1 + 3 * pages_per, PAGE, slots=3))
    chunked = jax.jit(lambda p, *a, ssm_rows: paged_prefill_chunk(
        p, cfg, *a, ssm_rows=ssm_rows, **kern))
    step = jax.jit(lambda p, *a: paged_decode_step(
        p, cfg, *a, return_logits=True, **kern))
    got = [[] for _ in lens]
    for r, n in enumerate(lens):
        for off in range(0, n, chunk):
            c = rows[r][off:min(off + chunk, n)]
            ids = np.zeros((1, chunk), np.int32)
            ids[0, :len(c)] = c
            logits, kp, vp, _ = chunked(
                params, jnp.asarray(ids), jnp.asarray([off]),
                jnp.asarray([len(c)]), kept[r:r + 1], kp, vp,
                ssm_rows=jnp.asarray([r + 1]))
        got[r].append(np.asarray(logits)[0])
    cur = np.asarray(lens, np.int32)
    for i in range(new - 1):
        tok = jnp.asarray([rows[r][lens[r] + i] for r in range(3)])
        logits, kp, vp, _ = step(params, tok, jnp.asarray(cur),
                                 jnp.asarray([True] * 3), kept, kp, vp)
        for r in range(3):
            got[r].append(np.asarray(logits)[r])
        cur += 1
    return [np.stack(g) for g in got], (kp, vp)


LENS, NEW = [41, 26, 67], 5
ROWS = [np.random.RandomState(21 + r).randint(1, 128, n + NEW).astype(np.int32)
        for r, n in enumerate(LENS)]


@KERNELS
@pytest.mark.parametrize("name,chunk", [("h256", 8), ("h256", 20), ("h256", 66),
                                        ("h128", 12)])
def test_chunked_prefill_then_decode_matches_reference(all_params, exact, name,
                                                       chunk, kern):
    """Across the chunk seam (chunks of 8, 20 and 66: 67 tokens end ONE
    token into their second chunk) and the delta rule's block seam, through
    pages that hold a head a layer, against the reference's full forward."""
    cfg, p = CONFIGS[name], all_params[name]
    got, _ = _through_the_cache(cfg, p, ROWS, LENS, NEW, chunk, kern)
    for r, n in enumerate(LENS):
        want = _reference(p, ROWS[r], cfg)[n - 1:n - 1 + NEW]
        np.testing.assert_allclose(got[r], want, atol=EXACT)


def test_the_state_and_window_a_row_leaves_are_the_reference_s(params, exact):
    _, (kp, vp) = _through_the_cache(CFG, params, ROWS, LENS, NEW, 20, {})
    hp = ref.hyper(CFG)
    for r, n in enumerate(LENS):
        fed = n + NEW - 1
        with jax.default_matmul_precision("highest"):
            _, _, states, windows = ref.decoder_logits(
                params, jnp.asarray(ROWS[r]), 0, new=1, hp=hp, fed=fed)
        np.testing.assert_allclose(np.asarray(kp["gdn"][:, r + 1]),
                                   np.asarray(states), atol=EXACT)
        np.testing.assert_allclose(np.asarray(vp["gdn"][:, r + 1]),
                                   np.asarray(windows), atol=EXACT)
        got = ref.state_verdict(kp["gdn"][:, r + 1], vp["gdn"][:, r + 1],
                                states, windows)
        assert max(got["ahead"], *got["behind"]) < 1e-3 and got["bf16_share"] < 0.01


@KERNELS
def test_padding_and_idle_lanes_leave_a_state_alone(params, kern):
    """A chunk's padded positions and a decode step's idle lanes move neither
    state nor window: rows other than the step's own, the scratch row
    included, stay bit for bit."""
    kp, vp = init_page_pool(CFG, 11, PAGE, slots=3)
    kp = {**kp, "gdn": jax.random.normal(jax.random.PRNGKey(1), kp["gdn"].shape)}
    vp = {**vp, "gdn": jax.random.normal(
        jax.random.PRNGKey(2), vp["gdn"].shape).astype(jnp.bfloat16)}
    table = jnp.asarray([[3, 1, 5, 7]], jnp.int32)
    ids = np.zeros((1, 16), np.int32)
    ids[0, :5] = IDS[:5]
    args = (jnp.asarray([8]), jnp.asarray([5]), table)
    _, kp2, vp2, _ = paged_prefill_chunk(params, CFG, jnp.asarray(ids), *args, kp, vp,
                                         ssm_rows=jnp.asarray([2]), **kern)
    for before, after in ((kp["gdn"], kp2["gdn"]), (vp["gdn"], vp2["gdn"])):
        np.testing.assert_array_equal(np.asarray(after[:, [0, 1, 3]], np.float32),
                                      np.asarray(before[:, [0, 1, 3]], np.float32))
        assert np.abs(np.asarray(after[:, 2], np.float32)
                      - np.asarray(before[:, 2], np.float32)).max() > 0
    # the same five tokens with no padding behind them leave the same row
    _, kp3, vp3, _ = paged_prefill_chunk(
        params, CFG, jnp.asarray(ids[:, :5]), *args, kp, vp,
        ssm_rows=jnp.asarray([2]), **kern)
    np.testing.assert_allclose(np.asarray(kp3["gdn"][:, 2]), np.asarray(kp2["gdn"][:, 2]),
                               atol=1e-5)
    np.testing.assert_array_equal(np.asarray(vp3["gdn"][:, 2], np.float32),
                                  np.asarray(vp2["gdn"][:, 2], np.float32))
    # a decode step with slot 1 alone active
    tables = jnp.zeros((3, 4), jnp.int32).at[1].set(table[0])
    _, kp4, vp4, _ = paged_decode_step(
        params, CFG, jnp.asarray([0, 9, 0]), jnp.asarray([0, 13, 0]),
        jnp.asarray([False, True, False]), tables, kp2, vp2, **kern)
    for before, after in ((kp2["gdn"], kp4["gdn"]), (vp2["gdn"], vp4["gdn"])):
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
    dirty_k = {**kp, "gdn": jnp.full_like(kp["gdn"], 7.0)}
    dirty_v = {**vp, "gdn": jnp.full_like(vp["gdn"], 5.0)}
    table = jnp.asarray([[3, 1, 5, 7]], jnp.int32)
    ids = jnp.asarray(IDS[None, :12])
    args = (jnp.asarray([0]), jnp.asarray([12]), table)
    clean, *_ = paged_prefill_chunk(params, CFG, ids, *args, kp, vp,
                                    ssm_rows=jnp.asarray([1]), **kern)
    reused, kp2, vp2, _ = paged_prefill_chunk(params, CFG, ids, *args, dirty_k,
                                              dirty_v, ssm_rows=jnp.asarray([1]),
                                              **kern)
    np.testing.assert_allclose(np.asarray(clean), np.asarray(reused), atol=1e-6)
    assert (np.asarray(kp2["gdn"][:, 2]) == 7.0).all()        # the other slot's
    assert (np.asarray(vp2["gdn"][:, 2]) == 5.0).all()
    with pytest.raises(ValueError, match="names its rows of the state pool"):
        paged_prefill_chunk(params, CFG, ids, *args, kp, vp)


def test_one_shot_prefill_refuses_a_gdn_pool(params):
    kp, vp = init_page_pool(CFG, 9, PAGE, slots=2)
    with pytest.raises(ConfigError, match="pools kv, gdn.*prefills in chunks"):
        paged_prefill(params, CFG, jnp.zeros((1, 16), jnp.int32), jnp.asarray([9]),
                      jnp.zeros((1, 2), jnp.int32), kp, vp)
    with pytest.raises(ConfigError, match="linear_attention layers.*serving: continuous"):
        dec.init_kv_cache(CFG, 1, 16)
    assert not pd.fusable(CFG)


@pytest.mark.parametrize("name", list(CONFIGS))
def test_kernel_probe_holds_every_kernel_to_its_plain_form(all_params, name):
    """The build-time probe: the page walk at the model's heads (a call a K/V
    head over pools that hold a head a layer at 256), the expert product,
    and the delta rule's two kernels."""
    from arkflow_tpu.tpu.serving_core import logits_parity

    out = gqa_kernel_probe(all_params[name], CONFIGS[name], PAGE, kernel_interpret=True)
    assert [n for n, _, _ in out] == [
        "paged_attention_decode", "paged_attention_chunk", "expert_product",
        "gdn_state_update", "gdn_chunk_scan"]
    for n, want, got in out:
        assert want.shape == got.shape and logits_parity(want, got)["ok"], n


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
    assert not server._fuses

    async def run(srv, prompts):
        return await asyncio.gather(*[srv.generate(p, 6) for p in prompts])

    outs = asyncio.run(run(server, PROMPTS))
    alone = [asyncio.run(run(_proc()._server, [p]))[0] for p in PROMPTS[3:]]
    assert outs[3:] == alone and [len(o) for o in outs] == [6] * 5
    assert max(t[2] for t in server._state_tenant) >= 2       # a slot was reused
    assert server._steps_ahead > 0
    assert len(server._free_pages) == server.num_pages - 1
    st = server.slot_state(0)
    assert st["state"].shape == (3, 4, 16, 128) and st["state"].dtype == np.float32
    assert st["window"].shape == (3, 3, 576) and st["tenancy"] >= 1


def test_server_counters_and_gauges_equal_a_hand_count():
    """One prompt of 21 tokens (chunks of 8: 8 + 8 + 5) and 6 new tokens, a
    share of the experts held."""
    proc = _proc({"experts_held": (2, 6)})
    server = proc._server
    names = ("arkflow_gen_moe_assignments_total", "arkflow_gen_ssm_tokens_total",
             "arkflow_gen_ssm_masked_total")
    before = {(n, k): _counter(n, kind=k).value
              for n in names for k in ("chunk", "decode")}
    held = sum(_counter("arkflow_gen_moe_held_assignments_total", kind=k).value
               for k in ("chunk", "decode"))
    resets = server.m_ssm_resets.value
    out = asyncio.run(server.generate(
        np.random.RandomState(1).randint(1, 128, 21).tolist(), 6))
    assert len(out) == 6
    d = {key: _counter(key[0], kind=key[1]).value - v for key, v in before.items()}
    # 5 expert layers (every layer routes), 3 choices a token
    assert d[names[0], "chunk"] == 21 * 3 * 5 and d[names[0], "decode"] == 5 * 3 * 5
    assert d[names[1], "chunk"] == 21 and d[names[2], "chunk"] == 3
    assert d[names[1], "decode"] == 5 and d[names[2], "decode"] == 5 * 2
    assert server.m_ssm_resets.value - resets == 1
    # 6 of 16 experts held: some of the 26 x 3 x 5 pairs land on them
    assert 0 < sum(_counter("arkflow_gen_moe_held_assignments_total", kind=k).value
                   for k in ("chunk", "decode")) - held < 26 * 3 * 5
    # the gauges read the spec: a page of K/V rows over the 2 attention
    # layers, a slot's states and windows over the 3 linear layers
    assert [g[1] for g in server.m_kv_live] == ["pages", "slots"]
    assert [g[2] for g in server.m_kv_live] == [
        PAGE * 4096, 3 * (4 * 16 * 128 * 4 + 3 * 576 * 2)]
    assert {m.labels["pool"] for m in global_registry().collect()
            if m.name == "arkflow_gen_kv_live_bytes"} >= {"kv", "gdn"}
    assert global_registry().gauge(
        "arkflow_gen_kv_bytes_per_token",
        labels={"model": "decoder_lm"}).value == kv_bytes_per_token(CFG) == 4096


@pytest.mark.parametrize("name", list(CONFIGS))
def test_the_paged_server_passes_its_probe_and_counts_its_walk(name):
    cfg = CONFIGS[name]
    proc = _proc({"head_dim": cfg.head_dim}, decode_kernel="paged",
                 kernel_interpret=True)
    server = proc._server
    parity = server.kernel_parity
    assert parity["ok"] and parity["kernels"] == [
        "paged_attention_decode", "paged_attention_chunk", "expert_product",
        "gdn_state_update", "gdn_chunk_scan"]
    walked = _counter("arkflow_gen_attn_pages_walked_total", kind="decode").value
    out = asyncio.run(server.generate(PROMPTS[1], 4))
    assert len(out) == 4
    assert _counter("arkflow_gen_attn_pages_walked_total", kind="decode").value > walked


# -- what is served and what is still refused ---------------------------------------


@pytest.mark.parametrize("extra,needle", [
    ({"mesh": {"tp": 2}}, "pools kv, gdn.*one chip"),
    ({"serving": "batch"}, "pools kv, gdn.*serving: continuous"),
    ({"prefill_chunk": 0}, "pools kv, gdn.*prefill_chunk > 0"),
    ({"prefix_cache_pages": 8}, "prefix_cache_pages.*pools kv, gdn"),
    ({"speculative_tokens": 2}, "speculative_tokens.*pools kv, gdn"),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else None)
def test_the_model_refuses_what_is_not_served_with_it(extra, needle):
    with pytest.raises(ConfigError, match=needle):
        _proc(**extra)


def test_the_model_refuses_kv_push_by_its_pools():
    proc = _proc()
    assert getattr(proc, "disagg", None) is None
    with pytest.raises(ConfigError, match="pools kv, gdn.*no wire form"):
        asyncio.run(proc._server.prefill_export([1, 2, 3], 2))


# -- the judge and the controls it refuses ------------------------------------------


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


@pytest.mark.parametrize("control", [
    "mantissa3", "no_qk_l2", "no_decay", "no_output_gate", "no_attention_gate",
    "no_shared_gate", "plain_norm_scale", "conv_row_late"])
def test_judge_refuses_the_control_by_its_tokens(params, exact, monkeypatch, control):
    """Tokens the program serves under a control are not the reference's.
    The cell's limits are sized for bfloat16 products behind eight expert
    layers on the chip; here the products are float32 and the program
    itself reads 0 (the test above), so the limits are held at a fiftieth."""
    CONTROLS[control](monkeypatch)
    tokens = [_greedy(params, CFG, p, 8) for p in JUDGED]
    verdict = ref.judge_rows(params, ref.hyper(CFG), JUDGED, tokens, longest=96,
                             shares=0.02)
    assert not verdict["ok"] and verdict["unexplained"] > 0


def _served_rows(slots=1, **extra):
    """Rows through the served path (chunks, then decode through pages and
    state rows), judged with the states and windows they left."""
    proc = _proc(slots=slots, **extra)
    server = proc._server
    for p in PROMPTS[:2]:
        asyncio.run(server.generate(p, 6))
    st = server.slot_state(0)
    return ref.judge_rows(
        proc.params, ref.hyper(proc.cfg), [list(st["prompt"])], [list(st["tokens"])],
        96, states=[st["state"]], windows=[st["window"]], shares=1e9)


@pytest.mark.parametrize("control,reads", [
    ("no_delta", "state_rel_err"), ("no_decay", "state_rel_err"),
    ("no_qk_l2", "state_rel_err"), ("conv_row_late", "state_rel_err"),
    ("no_dt_bias", "state_rel_err"), ("bf16_state", "state_bf16_values_share"),
    ("mantissa3", "state_rel_err")])
def test_the_state_a_row_left_sees_the_control(monkeypatch, control, reads):
    """Rule (d) through the served path, interpreted kernels and plain forms
    alike: a sound server's state is the reference's to the bfloat16
    products' rounding, and each control over the rule moves it past the
    limit — but a state HELD IN BFLOAT16, which no distance sees and the
    values' own bits do."""
    good = _served_rows()
    assert good["ok"] and good["state_rel_err"] < ref.STATE_REL_ERR
    assert good["state_bf16_values_share"] < ref.STATE_BF16_SHARE
    CONTROLS[control](monkeypatch)
    bad = _served_rows()
    assert not bad["ok"]
    limit = {"state_rel_err": ref.STATE_REL_ERR,
             "state_bf16_values_share": ref.STATE_BF16_SHARE}[reads]
    assert bad[reads] > limit
    if control == "bf16_state":  # the distance alone would pass it
        assert bad["state_rel_err"] < ref.STATE_REL_ERR


def _probe():
    proc = _proc(slots=1)
    server = proc._server
    for p in PROMPTS[:2]:
        asyncio.run(server.generate(p, 3))
    return ref.reuse_probe(server, proc.params, ref.hyper(proc.cfg), 7, 128)


def test_the_reuse_probe_sees_a_state_that_survives(monkeypatch):
    """Rule (e): after two requests over one slot, a one-token prompt's
    chunk leaves an empty window before its own input and the state of that
    token alone; under the control the earlier tenant's are still there."""
    good = _probe()
    assert good["ok"] and good["tenancy"] == 3 and good["before_abs_max"] == 0.0
    assert good["state_rel_err"] < ref.STATE_REL_ERR
    CONTROLS["state_survives"](monkeypatch)
    bad = _probe()
    assert not bad["ok"] and bad["before_abs_max"] > 0.0
    assert bad["state_rel_err"] > ref.STATE_REL_ERR


def test_judge_holds_the_float32_leaves():
    masters = dec.init(jax.random.PRNGKey(3), CFG)
    placed = jax.tree_util.tree_map(lambda leaf, dt: leaf.astype(dt), masters,
                                    dec.serve_dtypes(CFG))
    assert ref.stated_float32_leaves_differ(placed, masters) == 0
    for leaf, n in (("gdn_A_log", 3 * 4), ("shared_gate", 3 * 32)):
        was = placed["gdn_layers"][leaf]
        placed["gdn_layers"][leaf] = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), was)
        assert ref.stated_float32_leaves_differ(placed, masters) == n
        placed["gdn_layers"][leaf] = was


def test_the_cell_s_files_agree():
    """The configuration file carries every published key of the catalog's
    entry, cut only where ``reduced`` says, and builds the program's config."""
    import json

    with open(ROOT / "benchmark/configs/qwen3-next-80b-a3b-l8-ep8.json") as f:
        c = json.load(f)
    published = {"decoder_sparse_step": 1, "full_attention_interval": 4,
                 "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
                 "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
                 "linear_num_key_heads": 16, "linear_num_value_heads": 32,
                 "linear_value_head_dim": 128, "moe_intermediate_size": 512,
                 "num_attention_heads": 16, "num_experts_per_tok": 10,
                 "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
                 "rms_norm_eps": 1e-06, "rope_theta": 10000000,
                 "shared_expert_intermediate_size": 512}
    assert {k: c[k] for k in published} == published
    assert c["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (8, 64, 18992)
    assert c["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                              "vocab_size": 151936}
    assert c["layer_types"] == [
        LINEAR if (i + 1) % c["full_attention_interval"] else FULL for i in range(8)]
    cfg = dec.DecoderConfig(**{ours: c[theirs]
                               for ours, theirs in c["model_config_from"].items()})
    assert cfg.linear and cfg.held == (0, 64) and cfg.n_routed_experts == 512
    assert [p.name for p in cache_spec(cfg)] == ["kv", "gdn"]
    assert cfg.gqa(FULL).split_heads and cfg.gqa(FULL).rotary == 64
    with open(ROOT / "benchmark/traffic/report_backlog.json") as f:
        t = json.load(f)
    assert t["lengths"] == {"dist": "lognormal", "median": 2048, "sigma": 0.7,
                            "min": 256, "max": 8192}
    assert (t["batch_rows"], t["pool_rows"], t["stratify"], t["fill_rows"]) == (
        4, 256, 4, 16)
