"""A layer pattern of latent layers — sparse-indexed full layers beside
sliding-window layers — with a held share of routed experts, through the
paged serving path, held to the plain reference ``benchmark/references/
sparse_window_mla_moe.py`` on seeded weights at tiny widths: a rehearsal-size
``index_topk`` (16) and window (9), so 40- to 60-token rows cross both
bounds, and 4 of 16 experts held (Pallas in interpret mode).

The equations are held EXACTLY: with the program's products switched to
float32 (``exact``: the defaults of ``common.dense`` / ``embedding`` and the
index scores' operands; pools built float32) its logits are the reference's
to 2e-4 through the full forward and through chunked prefill and decode over
the cache — choices included, ties broken alike. At 16 of 60 positions and 4
of 16 experts nearly every position of the bfloat16 program has a choice
within a rounding of its boundary (and one position's other choice reaches
every later one through the keys it writes), so the bfloat16 program is held
to the reference only in the bulk (most positions within the tolerance), each
kernel to its plain-XLA form on its own inputs (the build-time probe), and
the paged program to the gather program.
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
from arkflow_tpu.models import common as cm
from arkflow_tpu.models import decoder as dec
from arkflow_tpu.models import paged_decode
from arkflow_tpu.models.paged_decode import (cache_spec, init_page_pool,
                                             kv_bytes_per_token,
                                             latent_kernel_probe,
                                             paged_decode_step, paged_prefill,
                                             paged_prefill_chunk,
                                             window_ring_pages)
from arkflow_tpu.obs import global_registry
from arkflow_tpu.ops import ragged_attention

ensure_plugins_loaded()


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/references/sparse_window_mla_moe.py", "ref_sparse_window")
ref_uncut = _load("benchmark/references/mla_moe_decoder.py", "ref_mla_moe_uncut")

FULL, SLIDING = dec.FULL, dec.SLIDING
TINY = dict(vocab_size=128, dim=32, layers=5, heads=4, ffn=64, max_seq=256,
            rope_theta=1e4, norm_eps=1e-5, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, rope_interleave=True,
            q_lora_rank=12, n_routed_experts=16, num_experts_per_tok=4,
            n_shared_experts=1, moe_intermediate_size=16,
            first_k_dense_replace=1, experts_held=(4, 4),
            # longer than ``layers``, as a published list cut in depth is
            layer_types=(FULL, FULL, SLIDING, SLIDING, SLIDING, FULL, SLIDING),
            sliding_window=9, swa_heads=2, swa_q_lora_rank=12,
            swa_kv_lora_rank=24, swa_qk_nope_head_dim=12,
            swa_qk_rope_head_dim=4, swa_v_head_dim=8, swa_rope_theta=5e3,
            swa_attention_gate_type="headwise", index_n_heads=4,
            index_head_dim=8, index_topk=16, attention_gate_type="headwise",
            apply_mla_qkv_lora_rescale=True)
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
    between 16 experts' scores (``init`` seeds +-0.01, for 128 and more)."""
    p = dec.init(jax.random.PRNGKey(3), CFG)
    for name in ("layers", "swa_layers"):
        p[name]["router_bias"] = jax.random.uniform(
            jax.random.PRNGKey(8), p[name]["router_bias"].shape, jnp.float32,
            -0.05, 0.05)
    return _round_like_placed(p, CFG)


_REFERENCE: dict = {}


def _reference(params, ids, cfg=CFG):
    """Reference logits [S, vocab] over one row."""
    key = (np.asarray(ids).tobytes(), cfg)
    if key not in _REFERENCE:
        with jax.default_matmul_precision("highest"):
            fn = jax.jit(lambda p, x: ref.decoder_logits(
                p, x, 0, new=len(ids), hp=ref.hyper(cfg))[0])
            _REFERENCE[key] = np.asarray(fn(params, jnp.asarray(ids)))
    return _REFERENCE[key]


@pytest.fixture
def exact(monkeypatch):
    """The program's products in float32 at ``highest`` precision: what is
    left between it and the reference is the order of float32 sums."""
    def index_scores(q_i, w, k_i):
        k_i = k_i.astype(jnp.bfloat16).astype(jnp.float32)  # as cached
        return jnp.einsum("bshk,bsh->bsk", jax.nn.relu(
            jnp.einsum("bshd,bkd->bshk", q_i, k_i)), w)

    def paged_scores(q_i, w, pages, layer, table, off, interpret=False):
        # the score kernel rounds its queries to bfloat16 by design: held
        # to its plain-XLA form by the probe, stood in for here
        return index_scores(q_i, w, pages[layer, table].reshape(
            table.shape[0], -1, pages.shape[-1]))

    monkeypatch.setattr(cm.dense, "__defaults__", (jnp.float32,))
    monkeypatch.setattr(cm.embedding, "__defaults__", (jnp.float32,))
    monkeypatch.setattr(dec, "index_scores", index_scores)
    monkeypatch.setattr(paged_decode, "index_scores", index_scores)
    monkeypatch.setattr(ragged_attention, "dsa_index_scores", paged_scores)
    with jax.default_matmul_precision("highest"):
        yield


EXACT = 2e-4


def _bulk(got, want) -> dict:
    """The bfloat16 program against the reference: no position further off
    than a choice the other way puts it (a whole different function would
    be), the median within ten logit tolerances."""
    tol = ref.logit_tolerance(want)
    diff = np.abs(got - want).max(-1)
    return {"ok": bool(np.median(diff) <= 10 * tol and diff.max() <= 40 * tol),
            "median": float(np.median(diff)), "max": float(diff.max()), "tol": tol}


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


# -- the cache spec --------------------------------------------------------------


def test_cache_spec_states_three_kinds_of_row():
    pools = {p.name: p for p in cache_spec(CFG)}
    assert set(pools) == {"latent", "index", "window"}
    # a rope key (4 lanes here, 64 published) is HELD in whole 128-lane rows
    assert (pools["latent"].layers, pools["latent"].widths) == (2, (16, 128))
    assert (pools["index"].layers, pools["index"].widths) == (2, (8,))
    assert (pools["window"].layers, pools["window"].widths,
            pools["window"].window) == (3, (24, 128), 9)
    assert kv_bytes_per_token(CFG) == 2 * (2 * 144 + 2 * 8 + 3 * 152)
    wide, rope = init_page_pool(CFG, 7, PAGE, window_pages=5)
    assert {k: v.shape for k, v in wide.items()} == {
        "latent": (2, 7, PAGE, 16), "index": (2, 7, PAGE, 8),
        "window": (3, 5, PAGE, 24)}
    assert {k: v.shape for k, v in rope.items()} == {
        "latent": (2, 7, PAGE, 128), "window": (3, 5, PAGE, 128)}
    # the published dots3 sizes: bytes a token a layer as held (1,152 and
    # 2,176 of them needed: the rope key's 64 lanes of 128)
    big = dataclasses.replace(
        CFG, kv_lora_rank=512, qk_rope_head_dim=64, index_head_dim=128,
        swa_kv_lora_rank=1024, swa_qk_rope_head_dim=64, sliding_window=513)
    by = {p.name: p.bytes_per_token // p.layers for p in cache_spec(big)}
    assert by == {"latent": 1280, "index": 256, "window": 2304}
    assert window_ring_pages(big, 16, 512) == (513 + 512 - 2) // 16 + 2
    # a model without a pattern keeps its two arrays
    plain = dec.DecoderConfig(**{k: v for k, v in TINY.items() if k in (
        "vocab_size", "dim", "heads", "ffn", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "rope_interleave", "n_routed_experts",
        "num_experts_per_tok", "n_shared_experts", "moe_intermediate_size",
        "first_k_dense_replace")}, layers=3)
    assert [p.name for p in cache_spec(plain)] == ["latent"]
    assert not plain.layered and window_ring_pages(plain, PAGE, 8) == 0


def test_layer_runs_follow_the_pattern():
    assert CFG.kinds == (FULL, FULL, SLIDING, SLIDING, SLIDING)
    assert dec.layer_runs(CFG) == [
        ("dense_layers", 0, 1, FULL, False, 0), ("layers", 0, 1, FULL, True, 1),
        ("swa_layers", 0, 3, SLIDING, True, 0)]
    deep = dataclasses.replace(
        CFG, layers=9, layer_types=(FULL,) + (FULL, SLIDING, SLIDING, SLIDING) * 2)
    assert [(r[0], r[1], r[2], r[5]) for r in dec.layer_runs(deep)] == [
        ("dense_layers", 0, 1, 0), ("layers", 0, 1, 1), ("swa_layers", 0, 3, 0),
        ("layers", 1, 2, 2), ("swa_layers", 3, 6, 3)]
    stacks = dec.layer_stacks(dec.init(jax.random.PRNGKey(0), deep), deep)
    assert [s[0]["attn_norm"]["scale"].shape[0] for s in stacks] == [1, 1, 3, 1, 3]


# -- the family's full forward == the reference ---------------------------------


def test_forward_matches_reference(params, exact):
    got = np.asarray(dec.forward(params, CFG, jnp.asarray(IDS)[None]))[0]
    np.testing.assert_allclose(got, _reference(params, IDS), atol=EXACT)


def test_bfloat16_forward_stays_with_the_reference(params):
    """Until the first choice that bfloat16 rounding can turn (no selection
    before position ``index_topk``; the window, the gate, the rescale and
    the held share all at work) the bfloat16 program is within the logit
    tolerance; after it, in the bulk."""
    got = np.asarray(dec.forward(params, CFG, jnp.asarray(IDS)[None]))[0]
    want = _reference(params, IDS)
    k = CFG.index_topk
    assert np.abs(got - want)[:k].max() <= 2 * ref.logit_tolerance(want)
    assert _bulk(got, want)["ok"], _bulk(got, want)


@pytest.mark.parametrize("ablation", [
    "no_window", "no_index", "no_gate", "no_rescale", "all_experts_held"])
def test_reference_comparison_detects(params, exact, ablation):
    """Every piece of the pattern left out of the program lands far outside
    what the correct program meets."""
    over = {"no_window": dict(sliding_window=64),
            "no_index": dict(index_topk=64),
            "no_gate": dict(attention_gate_type="", swa_attention_gate_type=""),
            "no_rescale": dict(apply_mla_qkv_lora_rescale=False),
            "all_experts_held": dict(experts_held=(0, 4))}[ablation]
    got = np.asarray(dec.forward(
        params, dataclasses.replace(CFG, **over), jnp.asarray(IDS)[None]))[0]
    assert np.abs(got - _reference(params, IDS)).max() > 100 * EXACT


def test_context_within_topk_is_plain_latent_attention(params):
    """While the context holds no more than ``index_topk`` keys the indexed
    layer attends all of them: the same logits, bit for bit, as the model
    without an indexer; one position later they part."""
    plain = dataclasses.replace(CFG, index_topk=0, index_n_heads=0,
                                index_head_dim=0)
    ids = jnp.asarray(IDS[:CFG.index_topk + 4])[None]
    a = np.asarray(dec.forward(params, CFG, ids))[0]
    b = np.asarray(dec.forward(params, plain, ids))[0]
    k = CFG.index_topk
    np.testing.assert_array_equal(a[:k], b[:k])
    assert np.abs(a[k:] - b[k:]).max() > 1e-3


# -- prefill then decode through the cache == the reference ---------------------


def _through_the_cache(params, rows, lens, new, chunk, kern, f32=False,
                       pages_per=8):
    """Chunked prefill of three ragged rows, then lockstep decode steps fed
    the rows' own tokens: every step's logits, a row at a time, and the
    counters of each chunk (with its offset and length) and decode step."""
    (kept, ring), cols = _tables(CFG, 3, pages_per, chunk)
    kp, vp = init_page_pool(CFG, 1 + 3 * pages_per, PAGE, 1 + 3 * cols)
    if f32:
        kp, vp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), (kp, vp))
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
            counts.append((off, len(c), [int(v) for v in stats]))
        got[r].append(np.asarray(logits)[0])
    cur = np.asarray(lens, np.int32)
    for i in range(new - 1):
        tok = jnp.asarray([rows[r][lens[r] + i] for r in range(3)])
        logits, kp, vp, stats = step(params, tok, jnp.asarray(cur),
                                     jnp.asarray([True] * 3), (kept, ring), kp, vp)
        counts.append((None, 3, [int(v) for v in stats]))
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
    """Chunks of 8 and of 12 straddle the window (9) and position
    ``index_topk`` (16) differently; the logits of every step are the
    reference's full-forward logits, and the counters a hand count. Through
    plain XLA, and through the Pallas kernels (the window's lower bound over
    the ring, the attention in place under the indexer's choice, the expert
    product)."""
    # tables of 8 and of 20 pages: 4 and 10 times ``index_topk`` positions
    got, counts = _through_the_cache(params, ROWS, LENS, NEW, chunk, kern,
                                     f32=True, pages_per=8 if chunk == 8 else 20)
    for r, n in enumerate(LENS):
        want = _reference(params, ROWS[r][:n + NEW - 1])
        np.testing.assert_allclose(got[r], want[n - 1:], atol=EXACT)
    for off, n, (pairs, _, _, here, picked, ctx) in counts:
        assert pairs == n * 4 * 4 and 0 <= here <= pairs
        if off is not None:  # a chunk: two indexed layers
            assert ctx == 2 * sum(range(off + 1, off + n + 1))
            assert picked == 2 * sum(min(CFG.index_topk, t + 1)
                                     for t in range(off, off + n))
        else:                # a decode step, every lane past position 16
            assert picked == 3 * 2 * CFG.index_topk


@KERNELS
@pytest.mark.parametrize("seed", [100, 101])
def test_replaying_the_program_s_choices_explains_its_bfloat16_logits(
        params, seed, kern):
    """The bfloat16 program against the float32 reference, a row teacher-
    forced through the cache: under the reference's OWN choices most
    positions past ``index_topk`` lie outside the logit tolerance (a near-tie
    that rounding turned, at the position or at an earlier one whose keys it
    reads); with the program's own selections and routing replayed through
    the reference (``forced``) every position is inside it. What the
    comparison that decides ``correct`` cannot see from the tokens alone."""
    replay = _load("tests/replay_choices.py", "replay_choices")
    row = np.random.RandomState(seed).randint(1, 128, 120).astype(np.int32)
    n, new = 90, 31
    got, sel, routed = replay.program_choices(params, CFG, row, n, PAGE, 8, **kern)
    assert got.shape == (new, CFG.vocab_size) and sel.shape == (2, 120, 120)
    assert (sel.sum(-1) == np.minimum(np.arange(120) + 1, CFG.index_topk)).all()
    with jax.default_matmul_precision("highest"):
        forced, _, flips = jax.jit(lambda p, x, m, r: ref.decoder_logits(
            p, x, n - 1, new=new, hp=ref.hyper(CFG), forced=(m, r)))(
                params, jnp.asarray(row), jnp.asarray(sel), jnp.asarray(routed))
    own = _reference(params, row)[n - 1:]
    tol = ref.logit_tolerance(own)
    assert (np.abs(got - own).max(-1) > 2 * tol).sum() >= new // 2
    assert np.abs(got - np.asarray(forced)).max() <= 2 * tol
    # the choices replayed differ from the reference's own inside the
    # near-tie band only (tiny widths: a tenth of the score spread)
    assert 0 < float(flips[:, 0].max()) < 0.1


def test_one_shot_prefill_refuses_a_layer_pattern(params):
    (kept, ring), cols = _tables(CFG, 1, 4, 8)
    kp, vp = init_page_pool(CFG, 5, PAGE, 1 + cols)
    with pytest.raises(ConfigError, match="prefills in chunks"):
        paged_prefill(params, CFG, jnp.zeros((1, 16), jnp.int32),
                      jnp.asarray([9]), (kept, ring), kp, vp)


# -- the kernels, each against its plain-XLA twin -------------------------------


def test_kernel_probe_covers_the_pattern_s_kernels(params):
    names = [name for name, ref_out, got in latent_kernel_probe(
        params, CFG, PAGE, kernel_interpret=True)]
    assert names == ["latent_attention_decode", "latent_attention_chunk",
                     "swa_latent_attention_decode", "swa_latent_attention_chunk",
                     "dsa_index_scores_decode", "dsa_index_scores_chunk",
                     "dsa_sparse_attention_decode",
                     "dsa_sparse_attention_chunk", "dsa_topk_select_decode",
                     "dsa_topk_select_chunk", "expert_product"]
    from arkflow_tpu.tpu.serving_core import logits_parity

    for name, want, got in latent_kernel_probe(params, CFG, PAGE,
                                               kernel_interpret=True):
        assert logits_parity(want, got)["ok"], name


# -- the held share ---------------------------------------------------------------


def test_the_expert_shares_add_up_to_the_uncut_layer(params):
    """Four chips hold 4 of the 16 experts each: their routed parts, with
    the shared expert counted once, are the uncut layer's output (the
    reference of the uncut layout, ``mla_moe_decoder.routed_experts``)."""
    uncut_cfg = dataclasses.replace(CFG, experts_held=None)
    whole = _round_like_placed(dec.init(jax.random.PRNGKey(3), uncut_cfg), uncut_cfg)
    lp = jax.tree_util.tree_map(lambda a: a[0], whole["layers"])
    y = jax.random.normal(jax.random.PRNGKey(4), (1, 24, CFG.dim), jnp.float32)
    hp = {"experts": 16, "top_k": 4, "scaling": CFG.routed_scaling_factor}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref_uncut.routed_experts(lp, y[0], hp)[0])
        shared = np.asarray(ref_uncut._swiglu(
            y[0], *[lp["experts"][k][16] for k in ("w_gate", "w_up", "w_down")]))
        total = np.zeros_like(want)
        loads = []
        for first in range(0, 16, 4):
            share = dataclasses.replace(CFG, experts_held=(first, 4))
            ex = {k: jnp.concatenate([v[first:first + 4], v[16:]])
                  for k, v in lp["experts"].items()}
            out, load = dec.routed_mlp({**lp, "experts": ex}, y, share)
            total += np.asarray(out, np.float32)[0] - shared
            loads.append(np.asarray(load))
            # the reference with the same share computes the same part
            part = np.asarray(ref.routed_experts(
                {**lp, "experts": ex}, y[0],
                {**hp, "held": (first, 4)})[0])
            np.testing.assert_allclose(np.asarray(out)[0], part, atol=2e-5)
    np.testing.assert_allclose(total + shared, want, atol=5e-5)
    # every share routes over all 16 and counts the same loads
    assert all((l == loads[0]).all() for l in loads) and loads[0].sum() == 24 * 4
    stats = np.asarray(dec.moe_step_stats(jnp.asarray(loads[:1]), (4, 4)))
    assert stats[0] == 96 and stats[3] == loads[0][4:8].sum() <= 96
    assert stats[1] == (loads[0][4:8] > 0).sum() and stats[2] == loads[0][4:8].max()


# -- the server: window pages freed as the window passes --------------------------


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


def test_window_pages_are_freed_and_never_read_again_kept_pages_are_kept():
    """Three prompts through the server. Every window page that is freed is
    at once overwritten with large values in the pool: were it read again
    (or a kept page freed early and reused) the tokens would differ from the
    undisturbed run's. Kept pages only ever grow while a request lives."""
    prompts = [np.random.RandomState(s).randint(1, 128, n).tolist()
               for s, n in ((1, 44), (2, 23), (3, 61))]

    def serve(poison: bool):
        proc = _proc()
        server = proc._server
        slide, freed, kept_low = server._slide_window, [], []

        def sliding(slot, first, last):
            before = dict(server._slot_win[slot])
            held = len(server._slot_pages[slot])
            slide(slot, first, last)
            # a page freed here may be taken again at once, for a page the
            # step is about to write: what it held is dead all the same
            gone = [before[i] for i in before if i not in server._slot_win[slot]]
            freed.extend(gone)
            kept_low.append(len(server._slot_pages[slot]) >= held)
            if poison and gone:
                idx = jnp.asarray(gone)
                for pools in (server.k_pages, server.v_pages):
                    pools["window"] = pools["window"].at[:, idx].set(3e4)

        server._slide_window = sliding
        freed0 = server.m_win_freed.value

        async def run():
            return await asyncio.gather(*[server.generate(p, 6) for p in prompts])

        outs = asyncio.run(run())
        return outs, freed, kept_low, server, server.m_win_freed.value - freed0

    clean, *_ = serve(False)
    outs, freed, kept_low, server, counted = serve(True)
    assert outs == clean and [len(o) for o in outs] == [6, 6, 6]
    # the window passed pages of every prompt: (n + 5 - 9) // 8 each at least
    assert counted == len(freed) >= sum((n + 5 - 9) // PAGE for n in (44, 23, 61))
    assert all(kept_low)
    # everything is back in both pools at the end
    assert len(server._win_free) == server.num_win_pages - 1
    assert len(server._free_pages) == server.num_pages - 1
    assert server.num_win_pages == 1 + 3 * window_ring_pages(CFG, PAGE, 8)


def test_server_counters_equal_a_hand_count():
    """One prompt of 21 tokens (chunks of 8: 8 + 8 + 5) and 6 new tokens."""
    proc = _proc()
    server = proc._server
    names = ("arkflow_gen_moe_assignments_total",
             "arkflow_gen_moe_held_assignments_total",
             "arkflow_gen_dsa_selected_total", "arkflow_gen_dsa_context_total")
    before = {(n, k): _counter(n, kind=k).value
              for n in names for k in ("chunk", "decode")}
    uploads = {k: server.m_uploads[k].value for k in ("chunk", "decode")}
    out = asyncio.run(server.generate(
        np.random.RandomState(1).randint(1, 128, 21).tolist(), 6))
    assert len(out) == 6
    d = {key: _counter(*key[:1], kind=key[1]).value - v for key, v in before.items()}
    assert d[names[0], "chunk"] == 21 * 4 * 4 and d[names[0], "decode"] == 5 * 4 * 4
    assert 0 < d[names[1], "chunk"] < d[names[0], "chunk"]
    # two indexed layers: keys in context sum(t + 1), attended min(16, t + 1)
    assert d[names[3], "chunk"] == 2 * sum(range(1, 22))
    assert d[names[2], "chunk"] == 2 * sum(min(16, t + 1) for t in range(21))
    assert d[names[3], "decode"] == 2 * sum(range(22, 27))
    assert d[names[2], "decode"] == 2 * 5 * 16
    # one host array a step, whatever the model counts on the device
    assert server.m_uploads["chunk"].value - uploads["chunk"] == 3
    assert server.m_uploads["decode"].value - uploads["decode"] == 5
    gauges = {p.name: global_registry().gauge(
        "arkflow_gen_kv_live_bytes",
        labels={"model": "decoder_lm", "pool": p.name}) for p in cache_spec(CFG)}
    assert set(gauges) == {"latent", "index", "window"}
    assert global_registry().gauge(
        "arkflow_gen_kv_bytes_per_token",
        labels={"model": "decoder_lm"}).value == kv_bytes_per_token(CFG)


@pytest.mark.parametrize("extra,needle", [
    ({"mesh": {"tp": 2}}, "one chip"),
    ({"serving": "batch"}, "serving: continuous"),
    ({"swap": {"drain_timeout": "1s"}}, "swap is not supported"),
    ({"integrity": {"probe_interval": "1s"}}, "integrity is not supported"),
    ({"dispatch_depth": 3}, "dispatch_depth > 2"),
    ({"prefix_cache_pages": 8}, "window pages"),
    ({"speculative_tokens": 2}, "indexed or sliding"),
    ({"prefill_chunk": 0}, "prefills in chunks"),
])
def test_pattern_model_refuses_what_is_not_served_with_it(extra, needle):
    with pytest.raises(ConfigError, match=needle):
        _proc(**extra)


def test_pattern_model_refuses_kv_push():
    proc = _proc()
    assert getattr(proc, "disagg", None) is None and proc.swapper is None
    with pytest.raises(ConfigError, match="no head axis"):
        asyncio.run(proc._server.prefill_export([1, 2, 3], 2))
    with pytest.raises(ConfigError, match="no head axis"):
        asyncio.run(proc._server.generate_from_pages({"done": False}))


@pytest.mark.parametrize("bad", [
    {"layer_types": (FULL, "linear_attention", SLIDING, SLIDING, SLIDING)},
    {"layer_types": (FULL, FULL)}, {"sliding_window": 0}, {"swa_heads": 0},
    {"swa_qk_rope_head_dim": 3}, {"swa_rope_theta": 0.0},
    {"swa_q_lora_rank": 0}, {"q_lora_rank": None},
    {"index_n_heads": 0}, {"index_head_dim": 2},
    {"attention_gate_type": "elementwise"},
    {"experts_held": (14, 4)}, {"experts_held": (0, 0)}, {"experts_held": (1,)},
    {"kv_lora_rank": 0},
], ids=lambda bad: "-".join(f"{k}={v}" for k, v in bad.items())[:60])
def test_model_config_values_not_served_raise(bad):
    with pytest.raises(ConfigError):
        dec.DecoderConfig(**{**TINY, **bad})


@pytest.mark.parametrize("bad,needle", [
    ({"sliding_window": 9}, "go together"),
    ({"index_topk": 4}, "latent-attention model"),
    ({"attention_gate_type": "headwise"}, "latent-attention model"),
    ({"apply_mla_qkv_lora_rescale": True}, "latent-attention model"),
    ({"swa_kv_lora_rank": 8}, "latent-attention model"),
    ({"layer_types": (FULL, FULL)}, None),
], ids=lambda v: "-".join(v) if isinstance(v, dict) else None)
def test_a_dense_model_refuses_the_latent_pattern_s_keys(bad, needle):
    """A per-head K/V model serves a layer pattern of its own since PR 40
    (``tests/test_window_gqa_moe.py``): ``layer_types`` alone passes, a
    window needs a sliding layer, the latent pattern's sizes stay refused."""
    sizes = dict(vocab_size=64, dim=32, layers=2, heads=4, kv_heads=2, ffn=64)
    if needle is None:
        assert not dec.DecoderConfig(**sizes, **bad).by_runs
        return
    with pytest.raises(ConfigError, match=needle):
        dec.DecoderConfig(**sizes, **bad)


def test_serve_dtypes_cover_every_leaf_and_state_the_choosers_float32():
    masters = dec.init(jax.random.PRNGKey(3), CFG)
    dtypes = dec.serve_dtypes(CFG)
    assert (jax.tree_util.tree_structure(masters)
            == jax.tree_util.tree_structure(dtypes))
    flat = dict(jax.tree_util.tree_flatten_with_path(dtypes)[0])
    for path, dt in flat.items():
        keys = [str(getattr(k, "key", k)) for k in path]
        chooser = any("router" in k or "norm" in k or "index_" in k for k in keys)
        assert (dt == jnp.float32) == chooser, keys
    assert jax.tree_util.tree_structure(dec.param_specs(CFG, {})) == \
        jax.tree_util.tree_structure(dtypes)


# -- a model without a pattern is what it was -----------------------------------------

#: sha256 (first 16 hex) of the tiny Kanana-2 layout's outputs at the parent
#: commit (PR 30): the full forward's logits; three prefill chunks and three
#: decode steps through the cache (logits, counters), gather then paged
#: kernels; a one-shot prefill's logits and both pools (the rope keys' own
#: lanes: since PR 44 the pool holds them in 128-lane rows, zeros behind)
PLAIN_GOLDEN = [
    '1681f486121feaa8', 'e5b551f1b54fc661', '7db5e01f1a915efa', '12d0ce652901d025',
    'fd454e1abc1dbd04', '9b67eade2e001352', 'b0fbac695a7e85fa', '7bc4815f061fa0d7',
    '854baff49ac1de5f', '877102554fa016b9', '854baff49ac1de5f', '30d22b1cc0d6d916',
    '854baff49ac1de5f', '942919ded059e784', '7db5e01f1a915efa', 'dc3e90014fd9dea9',
    'fd454e1abc1dbd04', 'd095e1997a33f797', 'b0fbac695a7e85fa', 'cd4cd04c97b175fa',
    '854baff49ac1de5f', '887bd7754aaea5d2', '854baff49ac1de5f', 'd7953eb67e95cf42',
    '854baff49ac1de5f', '80483f330a77932f', '1660f8f7ad46e729', '33f0fab2ec981c59']


def _plain_outputs():
    tiny = dict(vocab_size=128, dim=32, layers=3, heads=4, ffn=64, max_seq=128,
                rope_theta=1e4, norm_eps=1e-6, kv_lora_rank=16,
                qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                rope_interleave=True, n_routed_experts=8, num_experts_per_tok=2,
                n_shared_experts=2, moe_intermediate_size=16,
                first_k_dense_replace=1, routed_scaling_factor=2.448)
    cfg = dec.DecoderConfig(**tiny)
    params = dec.init(jax.random.PRNGKey(7), cfg)
    ids = np.random.RandomState(11).randint(1, 128, 40).astype(np.int32)
    out = [np.asarray(dec.forward(params, cfg, jnp.asarray(ids)[None]))]
    table = jnp.asarray([[3, 1, 5, 2, 7, 4, 6, 8]], jnp.int32)
    for kern in ({}, INTERPRET):
        kp, vp = init_page_pool(cfg, 1 + 8, 8)
        for off in range(0, 24, 8):
            logits, kp, vp, st = paged_prefill_chunk(
                params, cfg, jnp.asarray(ids[None, off:off + 8]),
                jnp.asarray([off]), jnp.asarray([8]), table, kp, vp, **kern)
            out += [np.asarray(logits), np.asarray(st)]
        for i in range(3):
            logits, kp, vp, st = paged_decode_step(
                params, cfg, jnp.asarray(ids[24 + i:25 + i]),
                jnp.asarray([24 + i]), jnp.asarray([True]), table, kp, vp,
                return_logits=True, **kern)
            out += [np.asarray(logits), np.asarray(st)]
    kp, vp = init_page_pool(cfg, 1 + 8, 8)
    logits, kp, vp, st = paged_prefill(
        params, cfg, jnp.asarray(ids[None, :16]), jnp.asarray([13]), table,
        kp, vp, return_logits=True)
    vp = np.asarray(vp, np.float32)
    assert vp.shape[-1] == 128 and not vp[..., 4:].any()
    return out + [np.asarray(logits), np.asarray(kp), vp[..., :4]]


def test_a_model_without_a_pattern_gives_bit_identical_outputs():
    got = [hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]
           for a in _plain_outputs()]
    assert got == PLAIN_GOLDEN


# -- the comparison that decides ``correct`` ----------------------------------------


def _teacher_row(params, prompt, new):
    """The reference's own greedy continuation of ``prompt``."""
    hp = ref.hyper(CFG)
    row = list(prompt)
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(lambda p, r, at: ref.decoder_logits(p, r, at, new=1, hp=hp)[0])
        for _ in range(new):
            padded = np.zeros((len(prompt) + new,), np.int32)
            padded[:len(row)] = row
            row.append(int(np.asarray(fn(params, padded, len(row) - 1))[0].argmax()))
    return row[len(prompt):]


def test_judge_accepts_the_reference_s_own_tokens_and_refuses_others(
        params, monkeypatch):
    prompt, new = IDS[:30].tolist(), 5
    served = _teacher_row(params, prompt, new)
    hp = ref.hyper(CFG)
    sound = ref.judge_rows(params, hp, [prompt], [served], longest=40)
    assert sound["ok"] and sound["positions_checked"] == new
    assert sound["unexplained"] == sound["rerouted"] == sound["reselected"] == 0
    # a token far under the largest logit that no alternative explains
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(ref.decoder_logits(
            params, jnp.asarray(prompt), len(prompt) - 1, new=1, hp=hp)[0])[0]
    bad = ref.judge_rows(params, hp, [prompt], [[int(logits.argmin())]], longest=40)
    assert not bad["ok"] and bad["unexplained"] == 1
    assert "step 0" in bad["first_unexplained"]


def test_judge_admits_a_reselection_only_within_the_index_margin(params):
    """Rule (d): the token the RE-SELECTED reference (index scores from
    bfloat16 operands) would choose, where it differs from the float32
    selection's, is accepted when the positions that changed sides lie
    within the margin, counted, and refused under a margin of zero."""
    hp = ref.hyper(CFG)
    found = None
    with jax.default_matmul_precision("highest"):
        for seed in range(40):
            ids = np.random.RandomState(100 + seed).randint(1, 128, 48)
            a, _, fa = ref.decoder_logits(params, jnp.asarray(ids), 47, new=1, hp=hp)
            b, _, fb = ref.decoder_logits(params, jnp.asarray(ids), 47, new=1,
                                          hp=hp, reselect=True)
            a, b = np.asarray(a)[0], np.asarray(b)[0]
            tol = ref.logit_tolerance(a)
            if float(np.asarray(fb)[0, 0]) > 0 and a.max() - a[b.argmax()] > 2 * tol:
                found = (ids.tolist(), int(b.argmax()), float(np.asarray(fb)[0, 0]))
                break
    if found is None:
        pytest.skip("no seed's rounding of the index operands changes a token")
    prompt, token, flip = found
    v = ref.judge_rows(params, hp, [prompt], [[token]], longest=56,
                       index_delta=2 * flip)
    assert (v["reselected"], v["unexplained"]) == (1, 0), v
    assert abs(v["widest_flip_reselected"] - flip) < 1e-6
    v = ref.judge_rows(params, hp, [prompt], [[token]], longest=56, index_delta=0.0)
    assert v["reselected"] == 0 and v["unexplained"] == 1 and not v["ok"]


def test_judge_holds_the_float32_leaves_the_indexer_s_too():
    masters = dec.init(jax.random.PRNGKey(3), CFG)
    placed = jax.tree_util.tree_map(lambda a, dt: a.astype(dt), masters,
                                    dec.serve_dtypes(CFG))
    assert ref.stated_float32_leaves_differ(placed, masters) == 0
    for stack, leaf in (("layers", "index_wq"), ("dense_layers", "index_w"),
                        ("swa_layers", "router")):
        w = masters[stack][leaf]["w"]
        bad = {**placed, stack: {**placed[stack], leaf: {
            "w": w.astype(jnp.bfloat16).astype(jnp.float32)}}}
        assert ref.stated_float32_leaves_differ(bad, masters) > w.size // 2


@pytest.mark.parametrize("control", ["weights_e4m3", "sound"])
def test_the_comparison_refuses_a_lower_precision(params, control):
    """Served through the program's own paged path: sound weights are
    accepted; weights rounded to 3 mantissa bits (e4m3's) are refused."""
    prompts = [np.random.RandomState(s).randint(1, 128, n).tolist()
               for s, n in ((1, 44), (2, 37), (3, 52), (4, 29))]
    new = 8
    served_params = params
    if control == "weights_e4m3":
        served_params = jax.tree_util.tree_map(
            lambda a, dt: a.astype(jnp.float8_e4m3fn).astype(jnp.float32)
            if dt == jnp.bfloat16 else a, params, dec.serve_dtypes(CFG))
    (kept, ring), cols = _tables(CFG, 1, 8, 8)
    chunked = jax.jit(lambda p, *a: paged_prefill_chunk(p, CFG, *a))
    step = jax.jit(lambda p, *a: paged_decode_step(p, CFG, *a))
    tokens = []
    for prompt in prompts:
        kp, vp = init_page_pool(CFG, 9, PAGE, 1 + cols)
        for off in range(0, len(prompt), 8):
            c = prompt[off:off + 8]
            ids = np.zeros((1, 8), np.int32)
            ids[0, :len(c)] = c
            logits, kp, vp, _ = chunked(
                served_params, jnp.asarray(ids), jnp.asarray([off]),
                jnp.asarray([len(c)]), (kept, ring), kp, vp)
        toks = [int(np.asarray(logits)[0].argmax())]
        for i in range(new - 1):
            nxt, kp, vp, _ = step(
                served_params, jnp.asarray(toks[-1:]),
                jnp.asarray([len(prompt) + i]), jnp.asarray([True]),
                (kept, ring), kp, vp)
            toks.append(int(np.asarray(nxt)[0]))
        tokens.append(toks)
    v = ref.judge_rows(params, ref.hyper(CFG), prompts, tokens, longest=64)
    assert v["ok"] == (control == "sound"), v
