"""Blocks of ONE mixer each (Nemotron-H: a Mamba-2 layer, a routed-expert
layer of two-matrix relu-squared experts, a position-free GQA layer) through
the paged cache, held to ``benchmark/references/nemotron_h.py`` at tiny sizes
on the CPU: ``MEMEM*E`` with a share of the experts held, heads of 16 so that
the state pool holds two heads a row (``ops/ssm_scan.heads_packed``), kernels
in interpret mode. In float32 (``exact``) the program's logits are the
reference's to 2e-4 through chunked prefill and decode; ONE processor is
built for the file (``served``: everything its cases read is taken while it
serves, in float32), so the file stays well under two minutes."""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import functools
import importlib.util
import inspect
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded
from arkflow_tpu.errors import ConfigError
from arkflow_tpu.models import common as cm
from arkflow_tpu.models import decoder as dec
from arkflow_tpu.models import paged_decode as pd
from arkflow_tpu.models.decoder import FULL, MAMBA, MOE
from arkflow_tpu.obs import global_registry
from arkflow_tpu.ops import moe_experts as me
from arkflow_tpu.ops import ssm_scan as ss

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/references/nemotron_h.py", "nemotron_h_reference")

TINY = dict(vocab_size=128, dim=32, layers=7, heads=4, kv_heads=2, head_dim=16,
            max_seq=256, norm_eps=1e-5, hybrid_override_pattern="MEMEM*EMEMEM*",
            full_attention_rope=False, mamba_n_heads=4, mamba_d_head=16,
            mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4,
            mamba_chunk_size=8, mlp_hidden_act="relu2", n_routed_experts=16,
            experts_held=(0, 8), num_experts_per_tok=3, n_shared_experts=1,
            moe_intermediate_size=16, moe_shared_expert_intermediate_size=32,
            routed_scaling_factor=2.5, router_bias_std=0.1, embed_init_std=0.5)
CFG = dec.DecoderConfig(**TINY)
PAGE = 8
INTERPRET = dict(attention_kernel="paged", kernel_interpret=True)
KERNELS = pytest.mark.parametrize("kern", [{}, INTERPRET], ids=["gather", "paged"])
EXACT = 2e-4
IDS = np.random.RandomState(5).randint(1, 128, 48).astype(np.int32)


def _params(cfg):
    """Seeded weights as placed."""
    return jax.tree_util.tree_map(
        lambda leaf, dt: leaf.astype(dt).astype(jnp.float32),
        dec.init(jax.random.PRNGKey(3), cfg), dec.serve_dtypes(cfg))


@pytest.fixture(scope="module")
def params():
    return _params(CFG)


@contextlib.contextmanager
def _exact():
    """The program's products in float32 at ``highest`` precision: what is
    left between it and the reference is the order of float32 sums."""
    was = cm.dense.__defaults__, cm.embedding.__defaults__
    cm.dense.__defaults__ = cm.embedding.__defaults__ = (jnp.float32,)
    try:
        with jax.default_matmul_precision("highest"):
            yield
    finally:
        cm.dense.__defaults__, cm.embedding.__defaults__ = was


@pytest.fixture
def exact():
    with _exact():
        yield


def _reference(params, ids, cfg=CFG):
    return np.asarray(ref.decoder_logits(params, np.asarray(ids)[None],
                                         ref.hyper(cfg))[0])


def _pools(cfg=CFG, slots=3, f32=True):
    kp, vp = pd.init_page_pool(cfg, 1 + slots * 8, PAGE, slots=slots)
    if f32:  # the pools' own rounding out of an exact comparison
        kp, vp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), (kp, vp))
    return kp, vp


@functools.lru_cache(maxsize=None)
def _steps(paged: bool):
    """The two step programs over three lanes / one chunk of 8, jitted once a
    kernel mode for the file (traced under ``exact``: every caller asks for
    it)."""
    kern = INTERPRET if paged else {}
    chunk = jax.jit(lambda p, ids, off, n, table, rows, kp, vp: pd.paged_prefill_chunk(
        p, CFG, ids, off, n, table, kp, vp, return_all=True, ssm_rows=rows, **kern))
    decode = jax.jit(lambda p, tok, lens, act, table, kp, vp: pd.paged_decode_step(
        p, CFG, tok, lens, act, table, kp, vp, return_logits=True, **kern))
    return chunk, decode


def _walk(params, ids, kern, pools=None, row=1, prefill=None):
    """``ids`` through the paged cache as a server feeds them: chunks of 8
    over the first ``prefill`` tokens (default: up to the last whole chunk),
    the rest a token a decode step on lane ``row - 1`` of three. Returns
    (logits [S, vocab], kp, vp)."""
    chunk, decode = _steps(bool(kern))
    kp, vp = pools or _pools()
    table = jnp.zeros((3, 8), jnp.int32).at[row - 1].set(
        jnp.arange(1 + 8 * (row - 1), 9 + 8 * (row - 1)))
    n, out = len(ids), []
    whole = (n - 1) // 8 * 8 if prefill is None else prefill
    for off in range(0, whole, 8):
        lg, kp, vp, *_ = chunk(params, jnp.asarray(ids[None, off:off + 8]),
                               jnp.asarray([off]), jnp.asarray([8]),
                               table[row - 1:row], jnp.asarray([row]), kp, vp)
        out.append(lg[0])
    active = jnp.arange(3) == row - 1
    for pos in range(whole, n):
        lg, kp, vp, *_ = decode(params, jnp.where(active, int(ids[pos]), 0),
                                jnp.where(active, pos, 0), active, table, kp, vp)
        out.append(lg[row - 1:row])
    return np.asarray(jnp.concatenate(out)), kp, vp


# -- the kinds: config, cache spec, layer runs -------------------------------------


def test_a_block_holds_one_mixer_and_the_pattern_spells_its_kind():
    assert CFG.kinds == (MAMBA, MOE, MAMBA, MOE, MAMBA, FULL, MOE)
    assert CFG.one_mixer and CFG.mamba and CFG.stateful and CFG.by_runs
    assert CFG.relu2 and CFG.routed and not (CFG.hybrid or CFG.conv or CFG.hetero)
    assert CFG.mamba_d_ssm == 64 and CFG.ssm_conv_dim == 64 + 2 * 2 * 16
    assert (CFG.expert_layers, CFG.shared_stack, CFG.attn_kinds) == (3, 2, (FULL,))
    assert dataclasses.replace(CFG, hybrid_override_pattern="",
                               layer_types=CFG.kinds) == dataclasses.replace(
        CFG, hybrid_override_pattern="")
    assert dec.layer_runs(CFG) == [
        ("mamba_layers", 0, 1, MAMBA, False, 0), ("moe_layers", 0, 1, MOE, True, 0),
        ("mamba_layers", 1, 2, MAMBA, False, 1), ("moe_layers", 1, 2, MOE, True, 1),
        ("mamba_layers", 2, 3, MAMBA, False, 2),
        ("dense_layers", 0, 1, FULL, False, 0), ("moe_layers", 2, 3, MOE, True, 2)]
    masters = dec.init(jax.random.PRNGKey(3), CFG)
    assert set(masters) == {"embed", "norm_out", "lm_head", "mamba_layers",
                            "moe_layers", "dense_layers"}
    assert set(masters["dense_layers"]) == {"attn_norm", "wq", "wk", "wv", "wo"}
    assert set(masters["moe_layers"]) == {"attn_norm", "router", "router_bias",
                                          "experts"}
    experts = masters["moe_layers"]["experts"]
    assert set(experts) == {"w_up", "w_down"}       # two matrices: no gate
    assert experts["w_up"].shape == (3, 8 + 2, 32, 16)
    assert masters["mamba_layers"]["ssm_in"]["w"].shape == (3, 32, 64 + 128 + 4)
    assert (jax.tree_util.tree_structure(masters)
            == jax.tree_util.tree_structure(dec.serve_dtypes(CFG)))


def test_an_expert_is_held_in_whole_lane_rows():
    """A width that is no multiple of 128 lanes is held at the next one,
    zeros behind it (``relu(0)^2`` adds nothing): 1,856 -> 1,920."""
    wide = dataclasses.replace(CFG, moe_intermediate_size=200,
                               moe_shared_expert_intermediate_size=400)
    assert (CFG.expert_width_held, wide.expert_width_held) == (16, 256)
    published = dataclasses.replace(CFG, moe_intermediate_size=1856,
                                    moe_shared_expert_intermediate_size=3712)
    assert published.expert_width_held == 1920
    ex = dec._init_ffn(iter(jax.random.split(jax.random.PRNGKey(0), 6)), wide,
                       True)["experts"]
    assert ex["w_up"].shape == (10, 32, 256) and ex["w_down"].shape == (10, 256, 32)
    assert not np.asarray(ex["w_up"][..., 200:]).any()
    assert not np.asarray(ex["w_down"][:, 200:]).any()
    assert np.asarray(ex["w_up"][..., :200]).all()


def test_cache_spec_counts_each_pool_over_its_own_layers():
    kv, ssm = pd.cache_spec(CFG)
    assert (kv.name, kv.layers, ssm.name, ssm.layers) == ("kv", 1, "ssm", 3)
    assert ssm.per_slot and ssm.widths == (64 * 16, 3 * 128)
    assert ssm.bytes_per_slot == 3 * (64 * 16 * 4 + 3 * 128 * 2)
    kp, vp = pd.init_page_pool(CFG, 9, PAGE, slots=2)
    # two heads of 16 a row of the state pool (a group's two: they share B, C)
    assert ss.heads_packed(4, 2, 16) == CFG.ssm_heads_packed == 2
    assert ss.heads_packed(64, 8, 64) == 2
    assert ss.heads_packed(32, 2, 128) == 1 and ss.heads_packed(8, 8, 64) == 1
    assert kp["ssm"].shape == (3, 3, 2, 16, 32) and kp["ssm"].dtype == jnp.float32
    assert vp["ssm"].shape == (3, 3, 3, 128) and kp["kv"].shape[0] == 1
    full = jax.random.normal(jax.random.PRNGKey(0), (5, 4, 16, 16))
    packed = ss.pack_state(full, 2)
    assert packed.shape == (5, 2, 16, 32)
    np.testing.assert_array_equal(packed[:, 1, :, 16:], full[:, 3])
    np.testing.assert_array_equal(ss.unpack_state(packed, 2), full)


@pytest.mark.parametrize("change,needle", [
    ({"hybrid_override_pattern": "MEMXM*E"}, "hybrid_override_pattern spells"),
    ({"hybrid_override_pattern": "MEM"}, "for at least the 7 layers"),
    ({"layer_types": ("mamba",) * 7}, "both name the layers and disagree"),
    ({"hybrid_override_pattern": "MEM-M*E"}, "dense MLP block.*not served yet"),
    ({"hybrid_override_pattern": "MEMEMEE"}, "at least one mamba layer.*and one "
                                              "full_attention layer"),
    ({"hybrid_override_pattern": "", "sliding_window": 8,
      "layer_types": ("mamba", "moe", "sliding_attention", "moe", "mamba",
                      "full_attention", "moe")},
     "not beside latent attention.*sliding_attention"),
    ({"n_routed_experts": 0, "experts_held": None, "num_experts_per_tok": 0},
     "a moe layer and n_routed_experts > 0 go together"),
    ({"first_k_dense_replace": 1}, "first_k_dense_replace 0"),
    ({"mlp_hidden_act": "silu"}, "two matrices, relu.*mlp_hidden_act 'relu2'"),
    ({"moe_shared_expert_intermediate_size": 24}, "a multiple of "
                                                  "moe_intermediate_size"),
    ({"moe_shared_expert_intermediate_size": 0}, "0 with n_shared_experts 0"),
    ({"scoring_func": "softmax", "topk_method": "greedy"}, "routes by sigmoid"),
    ({"full_attention_rope": True}, "position-free.*full_attention_rope false"),
    ({"qk_norm": True}, "neither qk_norm"),
    ({"mamba_n_heads": 3}, "mamba_d_ssm = mamba_n_heads x mamba_d_head"),
    ({"mamba_n_heads": 0, "mamba_d_head": 0, "mamba_d_state": 0},
     "mamba_d_ssm = mamba_n_heads"),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else None)
def test_config_refuses_in_a_sentence(change, needle):
    with pytest.raises(ConfigError, match=needle):
        dec.DecoderConfig(**{**TINY, **change})


def test_the_keys_of_one_mixer_blocks_belong_to_them():
    plain = dict(vocab_size=128, dim=32, layers=2, heads=4, kv_heads=2)
    for extra in ({"mlp_hidden_act": "relu2"},
                  {"moe_shared_expert_intermediate_size": 32}):
        with pytest.raises(ConfigError, match="belong to a model of one-mixer"):
            dec.DecoderConfig(**plain, **extra)
    with pytest.raises(ConfigError, match="without mamba_d_ssm"):
        dec.DecoderConfig(**plain, mamba_n_heads=4)


# -- the two new products, and the recurrence on packed rows ----------------------


def _routing(key, t, e, k, shared):
    idx = jnp.argsort(jax.random.uniform(key, (t, e)), axis=-1)[:, :k]
    cw = jax.nn.one_hot(idx, e).sum(1) * 0.4
    cw = cw.at[:, 3].set(0.0)            # an expert nobody chose is not read
    return jnp.concatenate([cw, jnp.ones((t, shared))], axis=-1)


@pytest.mark.parametrize("rows", [24, 300], ids=["one_tile", "grouped"])
def test_the_two_matrix_product_is_its_plain_form(rows):
    """``moe_expert_relu2`` — up to 128 rows the one-tile kernel, more the
    grouped one — against every expert over every token in plain XLA, on the
    second layer of a stack, bfloat16 as served."""
    assert me.runs_grouped(rows) == (rows > 128)
    k = jax.random.split(jax.random.PRNGKey(rows), 4)
    d, f, e = 64, 256, 10
    x = jax.random.normal(k[0], (rows, d)).astype(jnp.bfloat16)
    wu = (jax.random.normal(k[1], (2, e, d, f)) * d ** -0.5).astype(jnp.bfloat16)
    wd = (jax.random.normal(k[2], (2, e, f, d)) * f ** -0.5).astype(jnp.bfloat16)
    cw = _routing(k[3], rows, e - 2, 3, 2)
    want = me.expert_relu2_dense(x, cw, wu[1], wd[1]).astype(jnp.float32)
    got = me.moe_expert_relu2(x, cw, wu, wd, 1, interpret=True).astype(jnp.float32)
    by_hand = sum(
        cw[:, j:j + 1] * (jnp.square(jnp.maximum(
            x.astype(jnp.float32) @ wu[1, j].astype(jnp.float32), 0.0))
            @ wd[1, j].astype(jnp.float32)) for j in range(e))
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= 2 ** -6 * scale
    assert float(jnp.abs(want - by_hand).max()) <= 2 ** -5 * scale
    # one stack of three dims is its own layer 0
    np.testing.assert_array_equal(
        np.asarray(me.moe_expert_relu2(x, cw, wu[1], wd[1], interpret=True),
                   np.float32), np.asarray(got))


@pytest.mark.parametrize("step", ["update", "scan"])
def test_the_recurrence_on_packed_rows_is_the_recurrence(step):
    """A pool that holds two narrow heads a row: each kernel (interpreted)
    and each plain form on it against the plain form on a pool a head a row."""
    k = iter(jax.random.split(jax.random.PRNGKey(11), 8))
    h, g, n, p, b, t = 8, 2, 16, 32, 2, 40
    pack = ss.heads_packed(h, g, p)
    assert pack == 4
    full = jax.random.normal(next(k), (2, 4, h, n, p))
    packed = ss.pack_state(full, pack)
    rows, fresh = jnp.asarray([3, 1]), jnp.asarray([True, False])
    a = -jnp.exp(jax.random.normal(next(k), (h,)))
    shape = (b,) if step == "update" else (b, t)
    x = jax.random.normal(next(k), (*shape, h, p))
    dt = jax.nn.softplus(jax.random.normal(next(k), (*shape, h)))
    bm, cmat = (jax.random.normal(next(k), (*shape, g, n)) for _ in range(2))

    def run(pool, **kern):
        if step == "update":
            return ss.ssm_state_update(pool, 1, rows, x, dt, a, bm, cmat, **kern)
        return ss.ssm_chunk_scan(pool, 1, rows, fresh, x, dt, a, bm, cmat, 8, **kern)

    want_y, want_s = run(full)
    for kern in ({}, dict(kernel=True, interpret=True)):
        y, s = run(packed, **kern)
        assert s.shape == packed.shape
        np.testing.assert_allclose(y, want_y, atol=2e-5)
        np.testing.assert_allclose(ss.unpack_state(s, pack), want_s, atol=2e-5)
        np.testing.assert_array_equal(s[0], packed[0])      # the other layer
        np.testing.assert_array_equal(s[1, [0, 2]], packed[1, [0, 2]])


def test_the_probe_holds_each_kernel_to_its_plain_form(params):
    lines = pd.gqa_kernel_probe(params, CFG, PAGE, kernel_interpret=True)
    assert [name for name, _, _ in lines] == [
        "paged_attention_decode", "paged_attention_chunk", "expert_product",
        "ssm_state_update", "ssm_chunk_scan"]
    for name, want, got in lines:
        want, got = (np.asarray(v, np.float32) for v in (want, got))
        assert np.abs(got - want).max() <= 2 ** -6 * max(np.abs(want).max(), 1), name


# -- the model against the reference ----------------------------------------------


def test_forward_matches_reference(params, exact):
    got = np.asarray(dec.forward(params, CFG, jnp.asarray(IDS[None]))[0])
    np.testing.assert_allclose(got, _reference(params, IDS), atol=EXACT)


def _states_of(kp, vp, row):
    """Row ``row`` of the state pool as ``slot_state`` hands it on."""
    return (np.asarray(ss.unpack_state(kp["ssm"][:, row], 2), np.float32),
            np.asarray(vp["ssm"][:, row], np.float32))


@KERNELS
def test_chunked_prefill_then_decode_matches_reference(params, exact, kern):
    """32 tokens in chunks of 8 (the scan's block: state handed on between
    chunks), 7 more a decode step each, on lane 1 of three: logits, not
    tokens, at every position; and the state the walk left is the
    recurrence's own, to float32 sums."""
    got, kp, vp = _walk(params, IDS[:39], kern, row=2)
    np.testing.assert_allclose(got, _reference(params, IDS[:39]), atol=EXACT)
    _, want = ref.hidden_states(params, IDS[None, :39], ref.hyper(CFG))
    held, window = _states_of(kp, vp, 2)
    verdict = ref.state_verdict([held], [window], want)
    assert verdict["state_rel_err"] < 1e-5 and verdict["window_rel_err"] < 1e-5
    assert verdict["state_rel_err_behind_worst"] < 1e-4


def test_a_state_held_in_bfloat16_is_seen(params, exact):
    """Rule (d)'s other reading: the same walk with the state pool in
    bfloat16 — 8 tokens a chunk, 31 a decode step each, every step rounding
    the state — reads a thousandth and more where the float32 pool (above)
    reads a hundred-thousandth; on the chip 511 steps carry it past
    ``STATE_REL_ERR`` (``tools/nemotron_control.py bf16_state``)."""
    kp, vp = _pools()
    coarse = ({**kp, "ssm": kp["ssm"].astype(jnp.bfloat16)}, vp)
    _, kp, vp = _walk(params, IDS[:39], {}, pools=coarse, prefill=8)
    assert kp["ssm"].dtype == jnp.bfloat16
    _, want = ref.hidden_states(params, IDS[None, :39], ref.hyper(CFG))
    held, window = _states_of(kp, vp, 1)
    verdict = ref.state_verdict([held], [window], want)
    assert verdict["state_rel_err"] > 1e-3, verdict


def test_a_dropped_skip_or_a_rotation_is_seen(params, exact, monkeypatch):
    """What the comparison is for: ``D x_t`` left out of the program, or the
    attention layer rotated after all, moves the logits by far more than
    ``EXACT``."""
    want = _reference(params, IDS[:24])
    ids = jnp.asarray(IDS[None, :24])
    no_skip = {**params, "mamba_layers": {
        **params["mamba_layers"],
        "ssm_D": jnp.zeros_like(params["mamba_layers"]["ssm_D"])}}
    assert np.abs(np.asarray(dec.forward(no_skip, CFG, ids)[0]) - want).max() \
        > 100 * EXACT
    monkeypatch.setattr(dec, "qk_positioned", lambda lp, q, k, cfg, positions, kind=FULL: (
        dec._rope(q, positions, 10000.0), dec._rope(k, positions, 10000.0)))
    assert np.abs(np.asarray(dec.forward(params, CFG, ids)[0]) - want).max() \
        > 100 * EXACT


@KERNELS
def test_padding_and_idle_lanes_leave_every_other_state_alone(params, exact, kern):
    """A chunk's padded positions and a decode step's idle lanes: every row
    of the state pool but the writer's own (and the scratch row) is bit for
    bit what it was, states and conv windows alike; the writer's own row is
    what a chunk of its true tokens alone leaves."""
    chunk, decode = _steps(bool(kern))
    kp, vp = _pools()
    kp = {**kp, "ssm": jax.random.normal(jax.random.PRNGKey(9), kp["ssm"].shape)}
    vp = {**vp, "ssm": jax.random.normal(jax.random.PRNGKey(10), vp["ssm"].shape)}
    table = jnp.arange(1, 9, dtype=jnp.int32)[None]
    ids = jnp.asarray(IDS[None, :8])
    one = jnp.asarray([2])
    # 5 true tokens of 8, on row 2 of rows 0..3; then the same 5 and three others
    _, kp2, vp2, *_ = chunk(params, ids, jnp.asarray([0]), jnp.asarray([5]), table,
                            one, kp, vp)
    _, kp5, vp5, *_ = chunk(params, ids.at[:, 5:].set(99), jnp.asarray([0]),
                            jnp.asarray([5]), table, one, kp, vp)
    for before, padded, other in ((kp, kp2, kp5), (vp, vp2, vp5)):
        b, a, t = (np.asarray(x["ssm"], np.float32) for x in (before, padded, other))
        np.testing.assert_array_equal(a[:, [1, 3]], b[:, [1, 3]])
        assert np.abs(a[:, 2] - b[:, 2]).max() > 0
        np.testing.assert_array_equal(a[:, 2], t[:, 2])   # the padding's ids: unread
    # a decode step with lane 0 (row 1) live: rows 2 and 3 stay
    _, kp3, vp3, *_ = decode(
        params, jnp.asarray([7, 0, 0]), jnp.asarray([5, 0, 0]),
        jnp.asarray([True, False, False]),
        jnp.zeros((3, 8), jnp.int32).at[0].set(table[0]), kp2, vp2)
    for before, after in ((kp2, kp3), (vp2, vp3)):
        b, a = (np.asarray(x["ssm"], np.float32) for x in (before, after))
        np.testing.assert_array_equal(a[:, [2, 3]], b[:, [2, 3]])
        assert np.abs(a[:, 1] - b[:, 1]).max() > 0


def test_the_two_shares_of_an_expert_layer_add_up(params, exact):
    """Experts 0..7 and 8..15 of an ``E`` layer, the shared expert counted
    once, give the uncut reference's layer: the same router over 16 outputs,
    weights normalised over all the chosen, each share its own experts'
    part."""
    whole = dataclasses.replace(CFG, experts_held=None)
    layer = dec._init_ffn(iter(jax.random.split(jax.random.PRNGKey(21), 6)),
                          whole, True)
    layer = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), layer)
    for name in ("router", "router_bias"):   # float32 as stated
        layer[name] = dec._init_ffn(iter(jax.random.split(
            jax.random.PRNGKey(21), 6)), whole, True)[name]
    y = jax.random.normal(jax.random.PRNGKey(22), (1, 40, 32), jnp.float32)
    uncut = ref.routed_experts(layer, y[0], ref.hyper(whole))
    parts, loads = [], []
    for first in (0, 8):
        cfg = dataclasses.replace(CFG, experts_held=(first, 8))
        ex = {k: jnp.concatenate([v[first:first + 8], v[16:]])
              for k, v in layer["experts"].items()}
        out, load = dec.routed_mlp({**layer, "experts": ex}, y, cfg)
        shared = ref._relu2(                            # the shared expert alone
            y[0], jnp.concatenate(list(layer["experts"]["w_up"][16:]), axis=-1),
            jnp.concatenate(list(layer["experts"]["w_down"][16:]), axis=0))
        parts.append(np.asarray(out[0]) - np.asarray(shared))
        loads.append(np.asarray(load))
        # the reference is given the same share
        np.testing.assert_allclose(
            out[0], ref.routed_experts({**layer, "experts": ex}, y[0],
                                       ref.hyper(cfg)), atol=EXACT)
    np.testing.assert_array_equal(loads[0], loads[1])   # one router, all 16
    assert loads[0].sum() == 40 * 3
    np.testing.assert_allclose(parts[0] + parts[1] + np.asarray(shared),
                               np.asarray(uncut), atol=EXACT)
    assert np.abs(parts[0]).max() > 0.01 and np.abs(parts[1]).max() > 0.01


def test_the_reference_reads_its_experts_out_of_the_masters_at_the_published_width():
    """An expert layer's experts reach the reference as float32 masters on
    the host, a block at a time, and are cut to ``moe_intermediate_size``
    there: whatever a stack holds BEHIND the width (the program's zeros at
    1,856 -> 1,920) is not read, and nothing placed is."""
    layer = dec._init_ffn(iter(jax.random.split(jax.random.PRNGKey(23), 6)), CFG, True)
    y = jax.random.normal(jax.random.PRNGKey(24), (20, 32), jnp.float32)
    hp = ref.hyper(CFG)
    want = ref.routed_experts(layer, y, hp)
    behind = {"w_up": np.pad(np.asarray(layer["experts"]["w_up"]),
                             ((0, 0), (0, 0), (0, 8)), constant_values=3.0),
              "w_down": np.pad(np.asarray(layer["experts"]["w_down"]),
                               ((0, 0), (0, 8), (0, 0)), constant_values=3.0)}
    got = ref.experts_over_rows(layer, behind, [y], hp)[0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.abs(np.asarray(want)).max() > 0.01
    assert "placed" not in inspect.signature(ref.judge_rows).parameters


# -- ONE served model ------------------------------------------------------------------

PROMPTS = [np.random.RandomState(s).randint(1, 128, n).tolist()
           for s, n in ((1, 44), (2, 23), (3, 61), (4, 9), (5, 17))]
NEW = 6
COUNTERS = ("arkflow_gen_moe_assignments_total", "arkflow_gen_ssm_tokens_total",
            "arkflow_gen_ssm_masked_total")


def _counter(name, **labels):
    return global_registry().counter(name, labels={"model": "decoder_lm", **labels})


@pytest.fixture(scope="module")
def served():
    """The file's one processor, serving in float32: five prompts over three
    slots, and everything the cases below read — tokens, each slot's state
    and window, the counters' increase."""
    ensure_plugins_loaded()
    with _exact():
        proc = build_component("processor", {
            "type": "tpu_generate", "model": "decoder_lm", "model_config": TINY,
            "serving": "continuous", "max_input": 64, "max_new_tokens": NEW,
            "slots": 3, "page_size": PAGE, "seq_buckets": [16],
            "prefill_chunk": 8, "eos_id": -1, "decode_kernel": "gather",
            "seed": 3}, Resource())
        server = proc._server
        before = {(n, k): _counter(n, kind=k).value
                  for n in COUNTERS for k in ("chunk", "decode")}

        async def serve():
            return await asyncio.gather(*[server.generate(p, NEW) for p in PROMPTS])

        outs = asyncio.run(serve())
        states = [server.slot_state(s) for s in range(3)]
        windows = [np.asarray(jax.device_get(server.v_pages["ssm"][:, s + 1]),
                              np.float32) for s in range(3)]
    counted = {key: _counter(key[0], kind=key[1]).value - v
               for key, v in before.items()}
    return types.SimpleNamespace(proc=proc, server=server, outs=outs,
                                 states=states, windows=windows, counted=counted)


def test_the_server_runs_ahead_reuses_slots_and_counts(served):
    server = served.server
    assert server._stateful and server._ahead and not server._fuses
    assert not pd.fusable(CFG)
    assert [len(o) for o in served.outs] == [NEW] * 5
    assert max(t[2] for t in server._state_tenant) >= 2       # a slot was reused
    assert len(server._free_pages) == server.num_pages - 1
    st = served.states[0]
    assert st["state"].shape == (3, 4, 16, 16) and st["state"].dtype == np.float32
    tokens = sum(len(p) for p in PROMPTS)
    # 3 expert layers, 3 choices a token; a state advances a token a mamba
    # layer's step (counted once a step, not a layer)
    assert served.counted[COUNTERS[0], "chunk"] == tokens * 3 * 3
    assert served.counted[COUNTERS[0], "decode"] == 5 * (NEW - 1) * 3 * 3
    assert served.counted[COUNTERS[1], "chunk"] == tokens
    assert served.counted[COUNTERS[1], "decode"] == 5 * (NEW - 1)
    assert [g[1] for g in server.m_kv_live] == ["pages", "slots"]
    assert [g[2] for g in server.m_kv_live] == [
        PAGE * 1 * 2 * 2 * 16 * 2, 3 * (64 * 16 * 4 + 3 * 128 * 2)]


def test_the_served_tokens_are_the_reference_s_greedy_walk(served, params):
    """Each request's tokens among its neighbours are the argmax of the
    reference's logits over prompt + tokens, teacher-forced."""
    masters = served.proc.host_params
    for prompt, out in zip(PROMPTS, served.outs):
        ids = np.asarray(prompt + out, np.int32)
        logits = _reference(jax.tree_util.tree_map(
            lambda leaf, dt: jnp.asarray(leaf).astype(dt).astype(jnp.float32),
            masters, dec.serve_dtypes(CFG)), ids)
        want = logits[len(prompt) - 1:len(ids) - 1].argmax(-1).tolist()
        assert out == want


def _judged(served, **over):
    rows = [dict(prompt=st["prompt"], tokens=st["tokens"], state=st["state"],
                 window=win) for st, win in zip(served.states, served.windows)]
    for r in rows:
        r.update({k: f(r[k]) for k, f in over.items()})
    return ref.judge_rows(
        served.proc.host_params, ref.hyper(CFG), [r["prompt"] for r in rows],
        [r["tokens"] for r in rows], 80, [r["state"] for r in rows],
        [r["window"] for r in rows])


def test_judge_accepts_what_was_served(served):
    verdict = _judged(served)
    assert verdict["ok"], verdict
    assert verdict["positions_checked"] == 3 * NEW and verdict["wrong_on_decided"] == 0
    # float32 products; what is left is the pool's bfloat16 conv window
    assert verdict["state_rel_err"] < ref.STATE_REL_ERR / 2
    assert verdict["window_rel_err"] < ref.WINDOW_REL_ERR / 2
    assert ref.stated_float32_leaves_differ(served.proc.params,
                                            served.proc.host_params) == 0


@pytest.mark.parametrize("control", ["stale_state", "late_window", "no_skip",
                                     "other_tokens", "one_state_behind"])
def test_judge_refuses(served, control, monkeypatch):
    """Another slot's state, a window a position late, a reference WITH ``D
    x_t`` against a program without it (here: the reference without it
    against the program with it), tokens that are not the argmax, and ONE
    wrong state in one layer behind a router (the median over those layers
    passes it; their worst does not): each fails a limit of its own."""
    over = {}
    if control == "stale_state":
        other = served.states[1]["state"]
        over["state"] = lambda s: other if s is not other else served.states[0]["state"]
    elif control == "one_state_behind":
        mine = served.states[0]["state"]
        wrong = np.array(mine)
        wrong[1] = -wrong[1]
        over["state"] = lambda s: wrong if s is mine else s
    elif control == "late_window":
        over["window"] = lambda w: np.roll(w, 1, axis=1)
    elif control == "no_skip":
        real = ref.mamba2
        monkeypatch.setattr(ref, "mamba2",
                            lambda *a, **kw: real(*a, **{**kw, "skip": False}))
    else:
        over["tokens"] = lambda t: [(x + 1) % 128 for x in t]
    verdict = _judged(served, **over)
    assert not verdict["ok"], verdict
    reads = {"stale_state": "state_rel_err",
             "late_window": "window_rel_err",
             "one_state_behind": "state_rel_err_behind_worst"}.get(control)
    if control == "one_state_behind":
        assert verdict["state_rel_err_behind"] <= ref.STATE_REL_ERR_BEHIND
        assert verdict["state_rel_err"] <= verdict["state_rel_err_limit"]
    if reads:
        assert verdict[reads] > verdict[reads + "_limit"]
    else:
        assert verdict["wrong_on_decided"] > ref.FEW


# -- what is served and what is still refused -----------------------------------------

REFUSED = ("mesh_tp", "prefix_cache", "speculation", "one_shot_prefill", "kv_push",
           "batch", "swap", "integrity", "fused_chunk", "run_ahead_eos")


@pytest.mark.parametrize("feature", pd.FEATURES)
def test_the_union_of_its_rows_is_what_the_model_answers_with(feature):
    """``UNSERVED``: the model has the rows ``kv``, ``ssm``, ``routed`` and
    ``one_mixer`` and is refused what any is (the table's order), in the
    table's own sentences; ``run_ahead`` is served."""
    assert pd.cache_rows(CFG) == ("kv", "ssm", "routed", "one_mixer")
    why = pd.unserved(CFG, feature)
    assert (why is not None) == (feature in REFUSED)
    if why is not None:
        rows = [pd.UNSERVED[r].get(feature) for r in pd.cache_rows(CFG)]
        first = next(r for r in rows if r is not None)
        assert why == first.format(hc=1, pools="kv, ssm")


def test_one_shot_prefill_is_refused_by_the_pools(params):
    kp, vp = _pools(f32=False)
    with pytest.raises(ConfigError, match="pools kv, ssm.*prefills in chunks"):
        pd.paged_prefill(params, CFG, jnp.zeros((1, 16), jnp.int32),
                         jnp.asarray([9]), jnp.zeros((1, 2), jnp.int32), kp, vp)


# -- the cell's files ----------------------------------------------------------------


def test_the_cell_s_files_agree():
    """The configuration file builds the model at its published widths (and
    its rehearsal at tiny ones), states its cut, and BENCHMARK.json lists it
    with one cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"]
                 if c["name"] == "nemotron3-nano-30b-a3b-l13-ep2")
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    cells = [w for w in bench["workloads"] if w["config"] == entry["name"]]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        ("nemotron3_l13.agent_backlog", "agent_backlog", 1)]
    assert len(bench["per_layer"]) == 128
    with open(os.path.join(ROOT, entry["file"])) as f:
        c = json.load(f)
    assert c["published"] == {"num_hidden_layers": 52, "n_routed_experts": 128,
                              "vocab_size": 131072}
    for sizes in (c, {**c, **c["rehearse"]["model"]}):
        cfg = dec.DecoderConfig(**{ours: sizes[theirs] for ours, theirs
                                   in c["model_config_from"].items()})
        assert cfg.one_mixer and cfg.relu2 and cfg.kinds[:7] == CFG.kinds
    cfg = dec.DecoderConfig(**{ours: c[theirs] for ours, theirs
                               in c["model_config_from"].items()})
    assert (cfg.dim, cfg.layers, cfg.heads, cfg.kv_heads, cfg.dh) == (2688, 13, 32, 2, 128)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state,
            cfg.mamba_n_groups, cfg.mamba_d_conv, cfg.mamba_chunk_size) == (
        64, 64, 128, 8, 4, 128)
    assert (cfg.n_routed_experts, cfg.held, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.shared_stack) == (128, (0, 64), 6, 1856, 2)
    assert [cfg.kinds.count(k) for k in (MAMBA, MOE, FULL)] == [6, 5, 2]
    kv, ssm = pd.cache_spec(cfg)
    assert (kv.layers, kv.bytes_per_token, ssm.layers) == (2, 2048, 6)
    assert ssm.bytes_per_slot == 6 * (2 * 2 ** 20 + 36864)
