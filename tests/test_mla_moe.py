"""Latent attention (MLA) + dropless top-k routed experts through the paged
serving path, held to the plain reference ``benchmark/references/
mla_moe_decoder.py`` on seeded weights at tiny widths (Pallas in interpret
mode at the smallest shapes that tile).

Tolerances. The program multiplies in bfloat16 with float32 accumulation and
the reference in float32, on the same bfloat16-rounded weights: logits are
held to the reference's own rule (4 bf16 ulps of the largest logit). A
position whose 2nd-against-3rd biased router score (top-2 here) lies within
``ROUTER_DELTA`` of a tie at some expert layer may route differently after
bfloat16 rounding of the router's INPUT, and is excluded, as the reference's
rule (b) says; the seeds below leave most positions in. At width 32 one
bfloat16 rounding of an activation is a larger share of a logit than at width
2,048, and the correct program lands at 0.8 to 1.0 of the reference's
tolerance: the tests allow twice it, and every piece of the mathematics left
out lands 30 to 50 times above it (``test_reference_comparison_detects``
asks for 10).
"""

from __future__ import annotations

import asyncio
import dataclasses
import hashlib
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
from arkflow_tpu.models import decoder as dec
from arkflow_tpu.models.paged_decode import (init_page_pool, kv_bytes_per_token,
                                             paged_decode_step, paged_prefill,
                                             paged_prefill_chunk)
from arkflow_tpu.obs import global_registry
from arkflow_tpu.ops.moe_experts import expert_swiglu_dense, moe_expert_swiglu

ensure_plugins_loaded()


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/references/mla_moe_decoder.py", "ref_mla_moe")
placed_rules = _load("tests/test_generate_placed_params.py", "placed_rules")

TINY = dict(vocab_size=128, dim=32, layers=3, heads=4, ffn=64, max_seq=128,
            rope_theta=1e4, norm_eps=1e-6, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, rope_interleave=True,
            n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=2,
            moe_intermediate_size=16, first_k_dense_replace=1,
            routed_scaling_factor=2.448)
CFG = dec.DecoderConfig(**TINY)
PAGE = 8
INTERPRET = dict(attention_kernel="paged", kernel_interpret=True)


def _round_like_placed(params, cfg):
    """The float32 masters rounded as the processor places them, held in
    float32 again: the values the configuration states."""
    return jax.tree_util.tree_map(
        lambda leaf, dt: leaf.astype(dt).astype(jnp.float32), params,
        dec.serve_dtypes(cfg))


@pytest.fixture(scope="module")
def params():
    """Seeded weights; the selection bias at +-0.1, the size of the gaps
    between 8 experts' scores (``init`` seeds +-0.01, for 128)."""
    p = dec.init(jax.random.PRNGKey(7), CFG)
    p["layers"]["router_bias"] = jax.random.uniform(
        jax.random.PRNGKey(8), p["layers"]["router_bias"].shape, jnp.float32,
        -0.1, 0.1)
    return _round_like_placed(p, CFG)


_REFERENCE: dict = {}


def _reference(params, ids):
    """Reference logits [S, vocab] and router margins [S] over one row (of
    the one module-wide parameter tree: kept by the row's ids)."""
    key = np.asarray(ids).tobytes()
    if key not in _REFERENCE:
        with jax.default_matmul_precision("highest"):
            fn = jax.jit(lambda p, x: ref.decoder_logits(
                p, x, 0, new=len(ids), hp=ref.hyper(CFG)))
            logits, (near, _) = fn(params, jnp.asarray(ids))
            near = np.asarray(near)  # [S, expert layers, 4]: chosen | not
            _REFERENCE[key] = (np.asarray(logits),
                               (near[..., 1] - near[..., 2]).min(-1))
    return _REFERENCE[key]


#: positions whose chosen and first not-chosen expert score closer than this
#: may route otherwise in the program (bfloat16 products feed its router):
#: their logits are another function's and are left out of ``_agree``
NEAR_TIE = 4e-3


def _agree(got, want, margin) -> dict:
    """The logit tolerance (the reference's rule a) on the positions that no
    expert layer routes within ``NEAR_TIE``."""
    keep = margin >= NEAR_TIE
    tol = 2 * ref.logit_tolerance(want)
    diff = float(np.abs(got - want)[keep].max())
    return {"ok": diff <= tol and keep.mean() > 0.5, "diff": diff, "tol": tol,
            "kept": float(keep.mean())}


IDS = np.random.RandomState(11).randint(1, 128, 40).astype(np.int32)


# -- the family's full forward == the reference -------------------------------


def test_forward_matches_reference(params):
    got = np.asarray(dec.forward(params, CFG, jnp.asarray(IDS)[None]))[0]
    want, margin = _reference(params, IDS)
    v = _agree(got, want, margin)
    assert v["ok"], v


# -- what the comparison can tell apart ----------------------------------------


def _without(params, name):
    """The program's weights with one piece of the mathematics taken out."""
    p = jax.tree_util.tree_map(lambda a: a, params)
    if name == "no_selection_bias":
        p["layers"]["router_bias"] = jnp.zeros_like(p["layers"]["router_bias"])
    elif name == "no_shared_expert":
        ex = dict(p["layers"]["experts"])
        e = CFG.n_routed_experts
        ex["w_down"] = ex["w_down"].at[:, e:].set(0.0)
        p["layers"]["experts"] = ex
    return p


@pytest.mark.parametrize("ablation", [
    "no_selection_bias", "no_shared_expert", "no_scaling_factor",
    "no_latent_norm", "half_split_rope"])
def test_reference_comparison_detects(params, ablation, monkeypatch):
    """A program that left the named piece out fails the comparison of
    ``test_forward_matches_reference``: the program is run WITHOUT it and
    must disagree with the reference beyond the tolerance."""
    cfg, p = CFG, params
    if ablation in ("no_selection_bias", "no_shared_expert"):
        p = _without(params, ablation)
    elif ablation == "no_scaling_factor":
        cfg = dataclasses.replace(CFG, routed_scaling_factor=1.0)
    elif ablation == "half_split_rope":
        monkeypatch.setattr(dec, "_rope_interleaved", dec._rope)
    elif ablation == "no_latent_norm":
        monkeypatch.setattr(
            dec.cm, "rms_norm",
            lambda q, x, eps=1e-6, _f=dec.cm.rms_norm:
                x if x.shape[-1] == CFG.kv_lora_rank else _f(q, x, eps))
    got = np.asarray(dec.forward(p, cfg, jnp.asarray(IDS)[None]))[0]
    want, margin = _reference(params, IDS)
    v = _agree(got, want, margin)
    assert not v["ok"] and v["diff"] > 10 * v["tol"], (ablation, v)


def test_router_is_float32():
    """Two experts whose router columns differ by less than a bfloat16 ulp:
    float32 scores tell them apart, a bfloat16 router would tie them and
    take the lower index."""
    cfg = dataclasses.replace(CFG, num_experts_per_tok=1, n_shared_experts=0)
    w = np.full((CFG.dim, CFG.n_routed_experts), -1.0, np.float32)
    w[:, 2] = 2.0 ** -6
    w[:, 5] = 2.0 ** -6 * (1 + 2.0 ** -10)   # rounds to 2^-6 in bfloat16
    lp = {"router": {"w": jnp.asarray(w)},
          "router_bias": jnp.zeros((CFG.n_routed_experts,), jnp.float32)}
    y = jnp.ones((3, CFG.dim), jnp.bfloat16)
    cw, load = dec.route_topk(lp, y, cfg)
    assert np.asarray(load).tolist() == [0, 0, 0, 0, 0, 3, 0, 0]
    lp16 = {**lp, "router": {"w": jnp.asarray(w).astype(jnp.bfloat16)}}
    assert int(np.asarray(dec.route_topk(lp16, y, cfg)[1])[2]) == 3


# -- router rules ---------------------------------------------------------------


def test_router_selects_by_biased_and_weighs_by_unbiased_scores():
    rng = np.random.RandomState(3)
    lp = {"router": {"w": jnp.asarray(rng.normal(0, 0.3, (CFG.dim, 8)), jnp.float32)},
          "router_bias": jnp.asarray(rng.uniform(-0.3, 0.3, 8), jnp.float32)}
    y = jnp.asarray(rng.normal(size=(12, CFG.dim)), jnp.bfloat16)
    cw, load = dec.route_topk(lp, y, CFG)
    cw = np.asarray(cw)
    s = 1.0 / (1.0 + np.exp(-(np.asarray(y, np.float32) @ np.asarray(lp["router"]["w"]))))
    biased = s + np.asarray(lp["router_bias"])
    plain_choice = 0
    for t in range(12):
        chosen = np.sort(np.argsort(-biased[t])[:2])
        assert np.flatnonzero(cw[t, :8]).tolist() == chosen.tolist()
        want = s[t, chosen] / s[t, chosen].sum() * 2.448
        np.testing.assert_allclose(cw[t, chosen], want, rtol=1e-5)
        np.testing.assert_allclose(cw[t, :8].sum(), 2.448, rtol=1e-5)
        plain_choice += chosen.tolist() == np.sort(np.argsort(-s[t])[:2]).tolist()
    assert plain_choice < 12  # the bias really changed some selections
    assert (cw[:, 8:] == 1.0).all() and int(np.asarray(load).sum()) == 24


def _expert_layer(params, i=0):
    return jax.tree_util.tree_map(lambda a: a[i], params["layers"])


@pytest.mark.parametrize("kernel", [False, True])
def test_dropless_under_a_collapsed_router(params, kernel):
    """Every token to the same two experts: nothing is dropped or zeroed."""
    lp = dict(_expert_layer(params))
    bias = np.full(8, -5.0, np.float32)
    bias[[1, 6]] = 5.0
    lp["router_bias"] = jnp.asarray(bias)
    y = jnp.asarray(np.random.RandomState(5).normal(size=(1, 24, CFG.dim)), jnp.bfloat16)
    out, load = dec.routed_mlp(lp, y, CFG, kernel=kernel, interpret=True)
    assert np.asarray(load).tolist() == [0, 24, 0, 0, 0, 0, 24, 0]
    with jax.default_matmul_precision("highest"):
        want, _ = ref.routed_experts(lp, y[0].astype(jnp.float32), ref.hyper(CFG))
    want = np.asarray(want)
    err = np.abs(np.asarray(out[0], np.float32) - want).max()
    assert err <= 4 * 2.0 ** -8 * np.abs(want).max(), err
    assert (np.abs(np.asarray(out[0], np.float32)).max(axis=-1) > 0).all()


@pytest.mark.parametrize("kernel", [False, True])
def test_inactive_lanes_route_nowhere_and_count_nowhere(params, kernel):
    lp = _expert_layer(params)
    rng = np.random.RandomState(9)
    y = jnp.asarray(rng.normal(size=(6, 1, CFG.dim)), jnp.bfloat16)
    mask = jnp.asarray([True, False, True, True, False, True])[:, None]
    out, load = dec.routed_mlp(lp, y, CFG, token_mask=mask, kernel=kernel,
                               interpret=True)
    # other values in the dead lanes change nothing for the live ones
    y2 = y.at[jnp.asarray([1, 4])].set(jnp.asarray(rng.normal(size=(2, 1, CFG.dim)) * 50, jnp.bfloat16))
    out2, load2 = dec.routed_mlp(lp, y2, CFG, token_mask=mask, kernel=kernel,
                                 interpret=True)
    live = np.asarray([0, 2, 3, 5])
    np.testing.assert_array_equal(np.asarray(out)[live], np.asarray(out2)[live])
    np.testing.assert_array_equal(np.asarray(load), np.asarray(load2))
    assert int(np.asarray(load).sum()) == 4 * CFG.num_experts_per_tok
    alone, load_alone = dec.routed_mlp(lp, y[live], CFG, kernel=kernel, interpret=True)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(load_alone))
    assert (np.asarray(out)[[1, 4]] == 0).all()


# -- the expert product -----------------------------------------------------------


@pytest.mark.parametrize("tokens", [5, 16, 150])
def test_expert_product_equals_loop_over_experts(tokens):
    """Uneven group sizes, empty groups, a token tile that is padded and
    (150 rows) more than one tile."""
    rng = np.random.RandomState(tokens)
    e, d, f = 10, 64, 256
    x = jnp.asarray(rng.normal(size=(tokens, d)), jnp.bfloat16)
    cw = np.zeros((tokens, e), np.float32)
    for t in range(tokens):
        for j in rng.choice([0, 1, 1, 1, 2, 7], 2, replace=False):
            cw[t, j] = rng.uniform(0.1, 1.0)     # experts 3-6 and 8 stay empty
    cw[:, 9] = 1.0
    wg, wu = (jnp.asarray(rng.normal(size=(e, d, f)) / 8, jnp.bfloat16) for _ in "gu")
    wd = jnp.asarray(rng.normal(size=(e, f, d)) / 16, jnp.bfloat16)
    want = np.zeros((tokens, d), np.float32)
    xf = np.asarray(x, np.float32)
    for j in range(e):                           # the loop over experts
        rows = np.flatnonzero(cw[:, j])
        if rows.size:
            g = xf[rows] @ np.asarray(wg[j], np.float32)
            h = g / (1 + np.exp(-g)) * (xf[rows] @ np.asarray(wu[j], np.float32))
            want[rows] += cw[rows, j, None] * (h @ np.asarray(wd[j], np.float32))
    tol = 4 * 2.0 ** -8 * np.abs(want).max()
    got = moe_expert_swiglu(x, jnp.asarray(cw), wg, wu, wd, interpret=True)
    assert np.abs(np.asarray(got, np.float32) - want).max() <= tol
    dense = expert_swiglu_dense(x, jnp.asarray(cw), wg, wu, wd)
    assert np.abs(np.asarray(dense, np.float32) - want).max() <= tol


# -- prefill -> decode through the latent page pool == the reference ---------------


def _tables(n_rows, pages_per):
    """Non-contiguous page tables (page 0 is the scratch page)."""
    perm = np.random.RandomState(2).permutation(np.arange(1, 1 + n_rows * pages_per))
    return perm.reshape(n_rows, pages_per).astype(np.int32)


@pytest.mark.parametrize("kern", [{}, INTERPRET], ids=["gather", "paged"])
def test_chunked_prefill_then_decode_matches_reference(params, kern):
    """Three rows of ragged lengths over 3+ pages: chunked prefill (chunks of
    8), then four lockstep decode steps fed the reference's own tokens; the
    logits of every step are the reference's full-forward logits."""
    rng = np.random.RandomState(21)
    lens = [19, 26, 17]
    new = 4
    rows = [rng.randint(1, 128, n + new).astype(np.int32) for n in lens]
    pages_per = 5
    table = jnp.asarray(_tables(3, pages_per))
    kp, vp = init_page_pool(CFG, 1 + 3 * pages_per, PAGE)
    assert (kp.shape[-1], vp.shape[-1]) == (16, 128) and kp.ndim == 4  # rope as held
    chunk = jax.jit(lambda p, *a: paged_prefill_chunk(p, CFG, *a, **kern))
    step = jax.jit(lambda p, *a: paged_decode_step(
        p, CFG, *a, return_logits=True, **kern))
    got = [[] for _ in lens]
    for r, n in enumerate(lens):
        for off in range(0, n, 8):
            c = rows[r][off:min(off + 8, n)]
            ids = np.zeros((1, 8), np.int32)
            ids[0, :len(c)] = c
            logits, kp, vp, _ = chunk(params, jnp.asarray(ids), jnp.asarray([off]),
                                      jnp.asarray([len(c)]), table[r:r + 1], kp, vp)
        got[r].append(np.asarray(logits)[0])
    cur = np.asarray(lens, np.int32)
    for i in range(new - 1):
        tok = jnp.asarray([rows[r][lens[r] + i] for r in range(3)])
        logits, kp, vp, stats = step(params, tok, jnp.asarray(cur),
                                     jnp.asarray([True] * 3), table, kp, vp)
        assert int(stats[0]) == 3 * 2 * 2  # lanes x top-2 x expert layers
        for r in range(3):
            got[r].append(np.asarray(logits)[r])
        cur += 1
    for r, n in enumerate(lens):
        want, margin = _reference(params, rows[r][:n + new - 1])
        v = _agree(np.stack(got[r]), want[n - 1:], margin[n - 1:])
        assert v["ok"], (r, v)


def test_absorbed_form_equals_expanded_form(params):
    """The one-shot prefill attends in the published (expanded) form, the
    chunk path in the absorbed form over the pool (the smallest model: the
    dense layer and one expert layer)."""
    cfg = dataclasses.replace(CFG, layers=2)
    p = {**params, "layers": jax.tree_util.tree_map(lambda a: a[:1], params["layers"])}
    ids = jnp.asarray(IDS[None, :24])
    table = jnp.asarray(_tables(1, 3))
    pools = init_page_pool(cfg, 4, PAGE)
    lens = jnp.asarray([24])
    expanded, kp, vp, _ = paged_prefill(p, cfg, ids, lens, table, *pools,
                                        return_logits=True)
    for kern in ({}, INTERPRET):
        absorbed, kp2, vp2, _ = paged_prefill_chunk(
            p, cfg, ids, jnp.asarray([0]), lens, table, *pools, **kern)
        # each form may sit one tolerance from the float32 logits
        tol = 2 * ref.logit_tolerance(np.asarray(expanded))
        assert np.abs(np.asarray(absorbed) - np.asarray(expanded)).max() <= tol
        # the first layer's rows come from the same products; the second's
        # from inputs that went through the two forms
        for one, two in ((kp, kp2), (vp, vp2)):
            one, two = np.asarray(one, np.float32), np.asarray(two, np.float32)
            np.testing.assert_array_equal(one[0], two[0])
            assert np.abs(one[1] - two[1]).max() <= 2 * ref.logit_tolerance(one[1])


# -- placement: no step casts a weight ------------------------------------------------


def _steps(cfg):
    kp, vp = init_page_pool(cfg, 9, PAGE)
    table = jnp.asarray([[1, 3, 5, 7], [2, 4, 6, 8]], jnp.int32)
    ids = jnp.asarray(np.random.RandomState(5).randint(1, 128, (2, 6)), jnp.int32)
    lens = jnp.asarray([6, 4], jnp.int32)
    return {
        "prefill": lambda p: paged_prefill(p, cfg, ids, lens, table, kp, vp),
        "decode": lambda p: paged_decode_step(
            p, cfg, ids[:, 0], lens, jnp.asarray([True, True]), table, kp, vp),
        "chunk": lambda p: paged_prefill_chunk(
            p, cfg, ids[:, :3], lens, jnp.asarray([3, 2], jnp.int32), table, kp, vp),
        "chunk_kernels": lambda p: paged_prefill_chunk(
            p, cfg, ids[:, :3], lens, jnp.asarray([3, 2], jnp.int32), table, kp,
            vp, **INTERPRET),
    }


@pytest.mark.parametrize("step", ["prefill", "decode", "chunk", "chunk_kernels"])
def test_serve_dtypes_cover_every_new_leaf(step):
    masters = dec.init(jax.random.PRNGKey(1), CFG)
    dtypes = dec.serve_dtypes(CFG)
    assert (jax.tree_util.tree_structure(dtypes)
            == jax.tree_util.tree_structure(masters))
    placed = jax.tree_util.tree_map(lambda a, dt: a.astype(dt), masters, dtypes)
    kinds = {jax.tree_util.keystr(path): str(leaf.dtype) for path, leaf in
             jax.tree_util.tree_flatten_with_path(placed)[0]}
    for path, dtype in kinds.items():
        f32 = "'scale'" in path or "'router'" in path or "'router_bias'" in path
        assert dtype == ("float32" if f32 else "bfloat16"), path
    fn = _steps(CFG)[step]
    n = len(jax.tree_util.tree_leaves(placed))

    def casts(tree):
        closed = jax.make_jaxpr(fn)(tree)
        return placed_rules.param_casts(closed.jaxpr, closed.jaxpr.invars[:n])

    assert casts(placed) == []
    assert len(casts(masters)) >= sum(d == "bfloat16" for d in kinds.values()) - 1


# -- the server: counters, refusals ----------------------------------------------------


def _proc(model_config=None, **extra):
    cfg = {"type": "tpu_generate", "model": "decoder_lm",
           "model_config": model_config or TINY, "serving": "continuous",
           "max_input": 40, "max_new_tokens": 4, "slots": 4, "page_size": PAGE,
           "seq_buckets": [16], "prefill_chunk": 16, "eos_id": -1,
           "decode_kernel": "gather", "seed": 3, **extra}
    return build_component("processor", cfg, Resource())


def _moe_metric(kind):
    reg = global_registry()
    labels = {"model": "decoder_lm", "kind": kind}
    return (reg.counter("arkflow_gen_moe_assignments_total", labels=labels),
            reg.histogram("arkflow_gen_moe_experts_hit", labels=labels),
            reg.histogram("arkflow_gen_moe_max_load", labels=labels))


def test_server_counters_equal_a_hand_count():
    """Two prompts (one chunked: 20 tokens in chunks of 16; one one-shot: 9
    tokens) and 4 new tokens each. Pairs routed are tokens x top-2 x the two
    expert layers, whatever the routing; distinct experts and the largest
    load are recomputed from the reference's routing of the same tokens."""
    proc = _proc()
    server = proc._server
    kinds = ("decode", "chunk", "prefill", "fused", "fused_lanes")
    before = {k: (m[0].value, m[1].count, m[1].sum, m[2].count)
              for k in kinds for m in [_moe_metric(k)]}
    prompts = [np.random.RandomState(s).randint(1, 128, n).tolist()
               for s, n in ((1, 20), (2, 9))]

    async def run():
        return await asyncio.gather(*[server.generate(p, 4) for p in prompts])

    outs = asyncio.run(run())
    assert [len(o) for o in outs] == [4, 4]
    delta = {k: (m[0].value - before[k][0], m[1].count - before[k][1],
                 m[1].sum - before[k][2], m[2].count - before[k][3])
             for k in kinds for m in [_moe_metric(k)]}
    assert delta["chunk"][:2] == (20 * 2 * 2, 2)      # 16 + 4 tokens, 2 chunks
    assert delta["prefill"][:2] == (9 * 2 * 2, 1)
    # 3 decode steps a request (the first token comes from prefill); lanes
    # decode together when both are live, so count pairs, not steps. The
    # chunked prompt's two chunks rode the other request's first two decode
    # steps (PR 56): those steps' one lane counts under ``fused_lanes``,
    # their blocks — the lane and the chunk's 16 and 4 tokens — under
    # ``fused``, and ``decode`` takes none of either
    assert delta["fused_lanes"][:2] == (2 * 1 * 2 * 2, 2)
    assert delta["fused"][:2] == ((1 + 16 + 1 + 4) * 2 * 2, 2)
    assert delta["decode"][0] + delta["fused_lanes"][0] == 2 * 3 * 2 * 2
    assert delta["decode"][1] == delta["decode"][3] >= 3
    # the one-shot prefill's distinct experts: the reference's routing
    hp = ref.hyper(proc.cfg)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), proc.params)
    x = jnp.asarray(prompts[1], jnp.int32)
    hits = _reference_hits(params, x, hp)
    assert abs(delta["prefill"][2] - hits) <= 0.5  # a near-tie may move one
    gauge = global_registry().gauge("arkflow_gen_kv_bytes_per_token",
                                    labels={"model": "decoder_lm"})
    assert gauge.value == kv_bytes_per_token(proc.cfg) == 2 * (16 + 128) * 3  # as held


def _reference_hits(params, ids, hp) -> float:
    """Mean over expert layers of the distinct experts the reference routes
    the prompt's tokens to (the layers' inputs from the reference too)."""
    with jax.default_matmul_precision("highest"):
        x = ref._f32(params["embed"]["table"][ids])
        hits = []
        for stack, routed in ((params["dense_layers"], False), (params["layers"], True)):
            for i in range(stack["attn_norm"]["scale"].shape[0]):
                lp = jax.tree_util.tree_map(lambda a: a[i], stack)
                x = x + ref.latent_attention(
                    lp, ref._rms_norm(lp["attn_norm"]["scale"], x, hp["eps"]), hp)
                x_n = ref._rms_norm(lp["mlp_norm"]["scale"], x, hp["eps"])
                if routed:
                    idx = ref.route(lp, x_n, hp)[0]
                    hits.append(len(np.unique(np.asarray(idx))))
                    x = x + ref.routed_experts(lp, x_n, hp)[0]
                else:
                    x = x + ref._swiglu(x_n, lp["w_gate"]["w"], lp["w_up"]["w"],
                                        lp["w_down"]["w"])
    return float(np.mean(hits))


def test_kernel_probe_judges_each_kernel_on_given_routing():
    """The build-time parity probe of a latent model compares each Pallas
    kernel with its plain-XLA twin on its own inputs (a routing near-tie
    must not fail a kernel), and a kernel that is wrong fails the build."""
    proc = _proc(decode_kernel="paged", kernel_interpret=True)
    verdict = proc._server.kernel_parity
    assert verdict["ok"] and verdict["kernels"] == [
        "latent_attention_decode", "latent_attention_chunk", "expert_product"], verdict
    import arkflow_tpu.ops.moe_experts as ops

    real = ops.moe_expert_swiglu
    try:
        ops.moe_expert_swiglu = lambda *a, **kw: real(*a, **kw) * 1.5
        with pytest.raises(ConfigError, match="expert_product"):
            _proc(decode_kernel="paged", kernel_interpret=True)
    finally:
        ops.moe_expert_swiglu = real


@pytest.mark.parametrize("extra,needle", [
    ({"mesh": {"tp": 2}}, "one chip"),
    ({"serving": "batch"}, "serving: continuous"),
    ({"swap": {"drain_timeout": "1s"}}, "swap is not supported"),
    ({"integrity": {"probe_interval": "1s"}}, "integrity is not supported"),
    ({"dispatch_depth": 3}, "dispatch_depth > 2"),
])
def test_latent_model_refuses_what_cannot_carry_its_pages(extra, needle):
    with pytest.raises(ConfigError, match=needle):
        _proc(**extra)


def test_latent_model_refuses_kv_push_and_bad_routing_keys():
    proc = _proc()
    assert getattr(proc, "disagg", None) is None and proc.swapper is None
    with pytest.raises(ConfigError, match="no head axis"):
        asyncio.run(proc._server.prefill_export([1, 2, 3], 2))
    with pytest.raises(ConfigError, match="no head axis"):
        asyncio.run(proc._server.generate_from_pages({"done": False}))


@pytest.mark.parametrize("bad", [
    {"scoring_func": "softmax"}, {"n_group": 4}, {"q_lora_rank": 0},
    {"kv_lora_rank": 0}, {"num_experts_per_tok": 9},
    # accepted at their one published value only, and only together
    {"norm_topk_prob": False}, {"rope_interleave": False},
    {"n_routed_experts": 0}, {"first_k_dense_replace": 0},
    {"first_k_dense_replace": 3},
], ids=lambda bad: "-".join(f"{k}={v}" for k, v in bad.items()))
def test_model_config_values_not_implemented_raise(bad):
    with pytest.raises(ConfigError):
        dec.DecoderConfig(**{**TINY, **bad})


# -- the comparison that decides ``correct`` (rules a, b, c) ------------------------


def _served_row(params, prompt, new, width):
    """The reference's own greedy continuation of ``prompt`` (teacher of
    itself), and its jitted padded forward."""
    hp = ref.hyper(CFG)
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(lambda p, row, at, swaps: ref.decoder_logits(
            p, row, at, new=1, hp=hp, swaps=swaps))
    row = np.zeros((width,), np.int32)
    row[:len(prompt)] = prompt
    none = np.full((width, CFG.expert_layers, 2), -1, np.int32)
    for i in range(new):
        logits, _ = fn(params, row, len(prompt) - 1 + i, none)
        row[len(prompt) + i] = int(np.asarray(logits)[0].argmax())
    return row, fn, none


def test_judge_holds_tokens_to_the_reference_or_to_an_admitted_rerouting(
        params, monkeypatch):
    prompt, new, width = IDS[:20].tolist(), 5, 32
    row, fn, none = _served_row(params, prompt, new, width)
    served = row[len(prompt):len(prompt) + new].tolist()
    hp = ref.hyper(CFG)
    sound = ref.judge_rows(params, hp, [prompt], [served], width)
    assert sound["ok"] and sound["positions_checked"] == new
    assert sound["unexplained"] == sound["rerouted"] == 0

    # a served run that took the runner-up expert at one layer of one
    # position, where that changes the token: wrong by the reference's own
    # routing, accepted re-routed, refused again when the gap is not admitted
    found = None
    for i in range(new):
        at = len(prompt) - 1 + i
        logits, (near_s, near_e) = fn(params, row, at, none)
        tol = ref.logit_tolerance(np.asarray(logits))
        for gap, _, moves in ref.reroutings(np.asarray(near_s)[0],
                                            np.asarray(near_e)[0], 1.0):
            swaps = none.copy()
            for layer, drop, add in moves:
                swaps[at, layer] = (drop, add)
            other = np.asarray(fn(params, row, at, swaps)[0])[0]
            if np.asarray(logits)[0].max() - np.asarray(logits)[0][other.argmax()] > 2 * tol:
                found = found or (i, int(other.argmax()), gap)
    assert found, "no re-routing of the tiny model changes a token"
    i, token, gap = found
    flipped = served[:i] + [token]
    monkeypatch.setattr(ref, "REROUTED_SHARE", 1.0)
    v = ref.judge_rows(params, hp, [prompt], [flipped], width, delta=1.0)
    assert v["ok"] and (v["unexplained"], v["rerouted"]) == (0, 1), v
    assert v["widest_gap_rerouted"] <= gap + 1e-6 and v["reroute_forwards"] >= 1
    v = ref.judge_rows(params, hp, [prompt], [flipped], width, delta=gap / 2)
    assert not v["ok"] and v["unexplained"] == 1, v
    assert "step %d" % i in v["first_unexplained"]
    # ... which a run may have within its limit (one in 2,500 on the chip)
    monkeypatch.setattr(ref, "UNEXPLAINED_SHARE", 1.0)
    assert ref.judge_rows(params, hp, [prompt], [flipped], width, delta=gap / 2)["ok"]
    # the share of positions accepted only re-routed is limited too
    monkeypatch.setattr(ref, "REROUTED_SHARE", 0.1)
    assert not ref.judge_rows(params, hp, [prompt], [flipped], width, delta=1.0)["ok"]
    # a token that no admitted re-routing explains
    monkeypatch.setattr(ref, "UNEXPLAINED_SHARE", 0.01)
    logits = np.asarray(fn(params, row, len(prompt) - 1, none)[0])[0]
    v = ref.judge_rows(params, hp, [prompt], [[int(logits.argmin())]], width, delta=1.0)
    assert not v["ok"] and v["unexplained"] == 1


def test_judge_holds_the_float32_leaves_to_their_masters():
    """Rule (c): a router (or its bias, or a norm) served rounded to
    bfloat16 is not the configuration's, in either container."""
    masters = dec.init(jax.random.PRNGKey(3), CFG)
    placed = jax.tree_util.tree_map(lambda a, dt: a.astype(dt), masters,
                                    dec.serve_dtypes(CFG))
    assert ref.stated_float32_leaves_differ(placed, masters) == 0
    router = masters["layers"]["router"]["w"]
    for served in (router.astype(jnp.bfloat16),
                   router.astype(jnp.bfloat16).astype(jnp.float32)):
        bad = {**placed, "layers": {**placed["layers"], "router": {"w": served}}}
        assert ref.stated_float32_leaves_differ(bad, masters) > router.size // 2
    bad = {**placed, "norm_out": {"scale": placed["norm_out"]["scale"] * 1.001}}
    assert ref.stated_float32_leaves_differ(bad, masters) == CFG.dim


# -- the dense path is what it was ------------------------------------------------------

#: sha256 of the lowered text of the dense GQA programs, ``_decode`` then
#: ``_chunk``. ``*_wide``, a head of 128 lanes (what the per-head cells
#: serve): gather with the pools carried whole through the layer scan (PR
#: 32), paged with the kernel walking the page table itself (PR 41), its
#: chunk tile a K/V head at a time (PR 43) — recorded at PR 46's parent, which
#: PR 46 reproduces. At a head of 8 both were RE-RECORDED at PR 46 on purpose:
#: a head narrower than 128 lanes has row-major pools and the narrow-head
#: walk since (they stood at fdea09887a70df43, e5fbbb5138b92dea and
#: 1af74e9152f85a54, 2626a52b0b48a18d). A PR that changes the dense programs
#: on purpose recomputes them with this test's code.
#: PR 60 did, ``paged_wide`` alone (5cd67e6cd4990bd3, 108db1c0c9f52ed8 before
#: it): both products of the per-head kernel take the type the pools hold and
#: a chunk tile reads a head's rows out of the slot's own words.
#: PR 61 RE-RECORDED ``paged_wide`` alone (0243bd5358771527, 5f8510cd32802d47 before it): its walk is ``_page_walk``'s (a run of ``PAGE_RUN`` neighbours a copy out of pools that
#: ride as flat rows, a program's last step starting the next program's first
#: group): every program that holds ``_paged_kernel`` moved — a window call's
#: too, whose walk takes no runs but shares the copies and the hand-on — and
#: nothing else did
DENSE_HLO = {
    "gather": ("60ffd56343915631", "9992b028c6149b1d"),
    "paged": ("94a8d586436a9de5", "567d43b22ded3d2f"),
    "gather_wide": ("eaf5b975d560c31b", "502b93d7f55f0b71"),
    "paged_wide": ("54a4fa02fc5d16d7", "998bd77cc688572f"),
}


def _dense_hlo(case: str) -> tuple:
    """The hashes of ``_decode``'s and ``_chunk``'s lowered text at a head
    of 8 or, ``_wide``, of 128 lanes."""
    kern, _, wide = case.partition("_")
    cfg = dec.DecoderConfig(vocab_size=128, dim=32, layers=2, heads=4,
                            kv_heads=2, ffn=64, head_dim=128 if wide else 0)
    p = jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg))
    kp, vp = jax.eval_shape(lambda: init_page_pool(cfg, 9, 8))
    s, i32 = jax.ShapeDtypeStruct, jnp.int32
    kw = dict(attention_kernel=kern, kernel_interpret=True)
    decode = jax.jit(lambda p, tok, lens, act, table, kp, vp: paged_decode_step(
        p, cfg, tok, lens, act, table, kp, vp, return_logits=True, **kw))
    chunk = jax.jit(lambda p, ids, off, clen, table, kp, vp: paged_prefill_chunk(
        p, cfg, ids, off, clen, table, kp, vp, **kw))
    texts = (
        decode.lower(p, s((4,), i32), s((4,), i32), s((4,), bool),
                     s((4, 2), i32), kp, vp).as_text(),
        chunk.lower(p, s((1, 8), i32), s((1,), i32), s((1,), i32),
                    s((1, 2), i32), kp, vp).as_text())
    return tuple(hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts)


@pytest.mark.parametrize("case", sorted(DENSE_HLO))
def test_dense_programs_lower_to_the_same_text(case):
    assert _dense_hlo(case) == DENSE_HLO[case]


#: sha256 of the tiny dense model's greedy tokens — a 13- and a 9-token
#: prompt prefilled in two chunks of 8, then 8 decode steps — recorded at
#: PR 32's parent, whose layer scan sliced the pools. Unlike the text above
#: this does not move with the lowering: a change of the programs' form
#: serves these tokens or is not the same program. (Seed 3 leaves 0.031
#: between the two largest logits at every step; both kernels serve the same.)
DENSE_TOKENS = "ef130c011f7cffcf"


def _dense_tokens(kern: str):
    cfg = dec.DecoderConfig(vocab_size=128, dim=32, layers=2, heads=4,
                            kv_heads=2, ffn=64)
    p = dec.init(jax.random.PRNGKey(3), cfg)
    kp, vp = init_page_pool(cfg, 9, 8)
    kw = dict(attention_kernel=kern, kernel_interpret=True)
    table = jnp.asarray([[3, 1, 5, 0], [2, 7, 4, 0]], jnp.int32)
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(32), (2, 16), 1, 128))
    lens = np.asarray([13, 9])
    for off in (0, 8):
        logits, kp, vp = paged_prefill_chunk(
            p, cfg, jnp.asarray(prompts[:, off:off + 8]),
            jnp.full((2,), off, jnp.int32),
            jnp.asarray(np.clip(lens - off, 0, 8), jnp.int32), table, kp, vp, **kw)
    tokens, lens = [jnp.argmax(logits, -1)], jnp.asarray(lens, jnp.int32)
    for _ in range(8):
        logits, kp, vp = paged_decode_step(
            p, cfg, tokens[-1].astype(jnp.int32), lens, jnp.ones((2,), bool),
            table, kp, vp, return_logits=True, **kw)
        lens = lens + 1
        tokens.append(jnp.argmax(logits, -1))
    return np.stack(tokens).astype(np.int32)


@pytest.mark.parametrize("kern", sorted(DENSE_HLO))
def test_dense_programs_serve_the_parents_tokens(kern):
    tokens = _dense_tokens(kern)
    assert tokens.shape == (9, 2)
    assert hashlib.sha256(tokens.tobytes()).hexdigest()[:16] == DENSE_TOKENS
