"""The hybrid block (Falcon-H1: a Mamba-2 mixer beside GQA attention in every
layer) through ``decoder_lm``: the one-shot forward, the paged path with its
recurrent-state pool, the mixer's two kernels, the server's slot discipline
and what it refuses — tiny shapes, seeded weights, CPU (kernels interpreted),
each held to the plain reference ``benchmark/references/hybrid_ssm_decoder.py``
(the recurrence token by token from a zero state).
"""

from __future__ import annotations

import asyncio
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded
from arkflow_tpu.errors import ConfigError
from arkflow_tpu.models import decoder as dec
from arkflow_tpu.models.paged_decode import (cache_spec, init_page_pool,
                                             kv_bytes_per_token,
                                             paged_decode_step, paged_prefill,
                                             paged_prefill_chunk)
from arkflow_tpu.obs import global_registry
from arkflow_tpu.ops import ssm_scan as ss

ROOT = Path(__file__).resolve().parent.parent
ensure_plugins_loaded()


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, ROOT / path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmark/references/hybrid_ssm_decoder.py", "ref_hybrid_ssm")

#: the published shape in small: head_dim is not dim / heads, two groups, a
#: scan block (8) that divides neither prompt, every multiplier off 1
TINY = dict(vocab_size=128, dim=64, layers=2, heads=4, kv_heads=2, head_dim=8,
            ffn=96, rope_theta=100000000000, mamba_d_ssm=32, mamba_n_heads=2,
            mamba_d_head=16, mamba_d_state=16, mamba_n_groups=2,
            mamba_chunk_size=8, embedding_multiplier=5.6,
            attention_out_multiplier=0.5, key_multiplier=0.3,
            ssm_in_multiplier=0.25, ssm_out_multiplier=0.4,
            ssm_multipliers=[0.35, 0.25, 0.18, 0.5, 0.35],
            mlp_multipliers=[0.18, 0.3], lm_head_multiplier=0.0078125)
CFG = dec.DecoderConfig(**TINY)
PAGE = 4
IDS = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 128))
LENS = np.array([21, 13])


@pytest.fixture(scope="module")
def params():
    return dec.init(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def ref_logits(params):
    """The reference's logits at every position of both rows, [2, 40, vocab]."""
    hp = ref.hyper(CFG)
    hidden, _ = ref.hidden_states(params, IDS.astype(np.int32), hp)
    w = np.asarray(params["lm_head"]["w"])
    scale = np.asarray(params["norm_out"]["scale"])
    out = []
    with jax.default_matmul_precision("highest"):
        for h in hidden:
            h = ref._rms_norm(scale, h, hp["norm_eps"])
            cols = jnp.asarray(w).astype(jnp.bfloat16).astype(jnp.float32)
            out.append(np.asarray(h @ cols) * hp["lm_head_multiplier"])
    return np.stack(out)


def _tol(ref_logits) -> float:
    return ref.logit_tolerance(np.abs(ref_logits).max())


def test_forward_matches_the_reference(params, ref_logits):
    got = np.asarray(dec.forward(params, CFG, jnp.asarray(IDS)))
    assert got.shape == ref_logits.shape
    assert np.abs(got - ref_logits).max() <= _tol(ref_logits)
    # the head's blocks reduce to the same numbers as the whole head
    hp = ref.hyper(CFG)
    hidden = ref.hidden_states(params, IDS[:1].astype(np.int32), hp)[0][0]
    stats = ref.head_stats(params, hidden, ref_logits[0].argmax(-1), hp, block=50)
    assert np.allclose(stats["best"], ref_logits[0].max(-1), atol=1e-6)
    assert np.allclose(stats["served"], stats["best"])


def _prefill_in_chunks(params, chunk, kern, poison=False):
    """Both rows through ``paged_prefill_chunk`` ``chunk`` tokens at a time
    (a row whose prompt has ended rides on as an all-padded row). Returns
    (each row's last logits, pools, table, state rows)."""
    kw = dict(attention_kernel=kern, kernel_interpret=True)
    kp, vp = init_page_pool(CFG, 17, PAGE, slots=3)
    if poison:  # what an earlier tenant left: a first chunk must reset it
        kp, vp = {**kp, "ssm": kp["ssm"] + 7.0}, {**vp, "ssm": vp["ssm"] + 3.0}
    table = jnp.asarray(np.arange(1, 17).reshape(2, 8)[:, ::-1].copy(), jnp.int32)
    rows = jnp.asarray([3, 1], jnp.int32)
    last = [None, None]
    for off in range(0, int(LENS.max()), chunk):
        ids = np.zeros((2, chunk), np.int32)
        clen = np.clip(LENS - off, 0, chunk)
        for r in range(2):
            ids[r, :clen[r]] = IDS[r, off:off + clen[r]]
        logits, kp, vp = paged_prefill_chunk(
            params, CFG, jnp.asarray(ids), jnp.asarray(np.minimum(off, LENS), jnp.int32),
            jnp.asarray(clen, jnp.int32), table, kp, vp, ssm_rows=rows, **kw)
        for r in range(2):
            if clen[r] > 0 and off + clen[r] == LENS[r]:
                last[r] = np.asarray(logits[r])
    return last, kp, vp, table, rows


@pytest.mark.parametrize("kern", ["gather", "paged"])
@pytest.mark.parametrize("chunk", [5, 8])
def test_chunked_prefill_then_decode_match_the_reference(params, ref_logits,
                                                         chunk, kern):
    """Chunks that divide neither prompt, from POISONED state rows, then 8
    decode steps through the cache with an idle lane between the two:
    logits, not tokens, against the reference's full forward."""
    tol = _tol(ref_logits)
    last, kp, vp, table, _ = _prefill_in_chunks(params, chunk, kern, poison=True)
    for r in range(2):
        assert np.abs(last[r] - ref_logits[r, LENS[r] - 1]).max() <= tol
    # lanes are slots: row 0 sits in slot 2 (state row 3), row 1 in slot 0
    lens = np.array([LENS[1], 0, LENS[0]], np.int32)
    tab = np.zeros((3, 8), np.int32)
    tab[0], tab[2] = np.asarray(table[1]), np.asarray(table[0])
    act = jnp.asarray([True, False, True])
    idle = (np.asarray(kp["ssm"][:, 2]), np.asarray(vp["ssm"][:, 2]))
    for i in range(8):
        tok = np.array([IDS[1, LENS[1] + i], 0, IDS[0, LENS[0] + i]], np.int32)
        logits, kp, vp = paged_decode_step(
            params, CFG, jnp.asarray(tok), jnp.asarray(lens), act,
            jnp.asarray(tab), kp, vp, return_logits=True,
            attention_kernel=kern, kernel_interpret=True)
        assert np.abs(np.asarray(logits[2]) - ref_logits[0, LENS[0] + i]).max() <= tol
        assert np.abs(np.asarray(logits[0]) - ref_logits[1, LENS[1] + i]).max() <= tol
        lens = lens + np.array([1, 0, 1], np.int32)
    assert np.array_equal(idle[0], np.asarray(kp["ssm"][:, 2]))
    assert np.array_equal(idle[1], np.asarray(vp["ssm"][:, 2]))


def test_the_one_shot_prefill_refuses_a_recurrent_state(params):
    """``paged_prefill`` is told no slot: a hybrid model prefills through
    ``paged_prefill_chunk`` only, and that names its rows of the state pool."""
    kp, vp = init_page_pool(CFG, 17, PAGE, slots=3)
    table = jnp.asarray(np.arange(1, 17).reshape(2, 8), jnp.int32)
    ids, lens = jnp.asarray(IDS[:, :24]), jnp.asarray(LENS, jnp.int32)
    with pytest.raises(ConfigError, match="prefills in chunks"):
        paged_prefill(params, CFG, ids, lens, table, kp, vp)
    with pytest.raises(ValueError, match="ssm_rows"):
        paged_prefill_chunk(params, CFG, ids, jnp.zeros_like(lens), lens, table,
                            kp, vp)


# -- ops/ssm_scan.py: both forms against the recurrence ---------------------------


def _scan_case(seed=0, b=3, t=24, layers=2, rows_n=5, h=4, n=16, p=8, g=2):
    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    state = jax.random.normal(next(k), (layers, rows_n, h, n, p))
    x = jax.random.normal(next(k), (b, t, h, p))
    dt = jax.nn.softplus(jax.random.normal(next(k), (b, t, h)) - 1)
    lens = jnp.asarray([t, 13, 5][:b])
    dt = dt * (jnp.arange(t)[None, :, None] < lens[:, None, None])
    a = -jnp.exp(jax.random.normal(next(k), (h,)))
    bm = jax.random.normal(next(k), (b, t, g, n))
    cm = jax.random.normal(next(k), (b, t, g, n))
    return state, x, dt, a, bm, cm, np.asarray(lens)


def _recurrence(s0, x, dt, a, bm, cm):
    """Token by token: (y [b, T, H, P], the last state)."""
    h, g = x.shape[2], bm.shape[2]
    ys, s = [], s0
    for t in range(x.shape[1]):
        bh = jnp.repeat(bm[:, t], h // g, 1)
        ch = jnp.repeat(cm[:, t], h // g, 1)
        s = (jnp.exp(dt[:, t] * a)[..., None, None] * s
             + bh[..., None] * (x[:, t] * dt[:, t, :, None])[:, :, None, :])
        ys.append(jnp.einsum("bhnp,bhn->bhp", s, ch))
    return jnp.stack(ys, 1), s


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
@pytest.mark.parametrize("chunk", [8, 24, 7])
def test_chunk_scan_is_the_recurrence(chunk, kernel):
    """Blocks of 8, one block, and a block size that does not divide the
    chunk (then one block); rows of 24, 13 and 5 valid tokens, one of them
    fresh; the other rows and the other layer untouched, bit for bit."""
    state, x, dt, a, bm, cm, lens = _scan_case()
    rows, fresh = jnp.asarray([3, 1, 4]), jnp.asarray([False, True, False])
    with jax.default_matmul_precision("highest"):
        s0 = jnp.where(fresh[:, None, None, None], 0.0, state[1, rows])
        want_y, want_s = _recurrence(s0, x, dt, a, bm, cm)
        y, pool = ss.ssm_chunk_scan(state, 1, rows, fresh, x, dt, a, bm, cm,
                                    chunk, kernel=kernel, interpret=True)
    valid = np.arange(x.shape[1])[None, :] < lens[:, None]
    assert np.abs(np.asarray(y - want_y))[valid].max() < 5e-5
    assert np.abs(np.asarray(pool[1, rows] - want_s)).max() < 5e-5
    assert np.array_equal(np.asarray(pool[0]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(pool[1, jnp.asarray([0, 2])]),
                          np.asarray(state[1, jnp.asarray([0, 2])]))


@pytest.mark.parametrize("kernel", [False, True], ids=["plain", "kernel"])
def test_state_update_is_one_step_of_the_recurrence(kernel):
    """One token a lane; a lane with a zero step (idle: row 0) changes
    nothing, its row included."""
    state, x, dt, a, bm, cm, _ = _scan_case(seed=1)
    rows = jnp.asarray([3, 0, 4])
    dt0 = dt[:, 0].at[1].set(0.0)
    want_y, want_s = _recurrence(state[1, rows], x[:, :1], dt0[:, None], a,
                                 bm[:, :1], cm[:, :1])
    y, pool = ss.ssm_state_update(state, 1, rows, x[:, 0], dt0, a, bm[:, 0],
                                  cm[:, 0], kernel=kernel, interpret=True)
    assert np.abs(np.asarray(y - want_y[:, 0])).max() < 5e-6
    assert np.abs(np.asarray(pool[1, rows] - want_s)).max() < 5e-6
    assert np.array_equal(np.asarray(pool[1, 0]), np.asarray(state[1, 0]))
    assert np.array_equal(np.asarray(pool[0]), np.asarray(state[0]))


@pytest.mark.parametrize("kern", ["gather", "paged"])
def test_padding_and_idle_lanes_leave_every_other_state_bit_identical(params, kern):
    """A chunk that is all padding, a chunk with a padded tail, and a decode
    step with idle lanes: the state rows and conv windows of every slot
    they do not advance stay bit-identical; the padded tail leaves the
    state of the tokens before it (the same chunk cut to its valid length
    ends in the same state and window)."""
    kw = dict(attention_kernel=kern, kernel_interpret=True)
    _, kp, vp, table, rows = _prefill_in_chunks(params, 8, kern)
    before = (np.asarray(kp["ssm"]), np.asarray(vp["ssm"]))
    # an all-padded chunk for row 0's slot, mid-prompt
    _, kp1, vp1 = paged_prefill_chunk(
        params, CFG, jnp.zeros((1, 8), jnp.int32), jnp.asarray([21], jnp.int32),
        jnp.asarray([0], jnp.int32), table[:1], kp, vp, ssm_rows=rows[:1], **kw)
    assert np.array_equal(before[0], np.asarray(kp1["ssm"]))
    assert np.array_equal(before[1], np.asarray(vp1["ssm"]))
    # 3 valid tokens then 5 of padding: only row 3 of the pools moves, and
    # to where the 3 tokens alone take it
    ids = np.zeros((1, 8), np.int32)
    ids[0, :3] = IDS[0, 21:24]
    args = (jnp.asarray([21], jnp.int32), jnp.asarray([3], jnp.int32), table[:1])
    _, kp2, vp2 = paged_prefill_chunk(params, CFG, jnp.asarray(ids), *args, kp, vp,
                                      ssm_rows=rows[:1], **kw)
    _, kp3, vp3 = paged_prefill_chunk(params, CFG, jnp.asarray(ids[:, :3]), *args,
                                      kp, vp, ssm_rows=rows[:1], **kw)
    others = np.array([0, 1, 2])
    for pool, cut, was in ((kp2, kp3, before[0]), (vp2, vp3, before[1])):
        got = np.asarray(pool["ssm"])
        assert np.array_equal(got[:, others], was[:, others])
        assert not np.array_equal(got[:, 3], was[:, 3])
        assert np.allclose(got[:, 3], np.asarray(cut["ssm"])[:, 3], atol=1e-5)
    assert np.array_equal(np.asarray(vp2["ssm"]), np.asarray(vp3["ssm"]))
    # a decode step with lanes 0 and 1 idle: only slot 2's row (3) moves
    _, kp4, vp4 = paged_decode_step(
        params, CFG, jnp.asarray([5, 6, 7], jnp.int32),
        jnp.asarray([0, 0, 21], jnp.int32), jnp.asarray([False, False, True]),
        jnp.concatenate([jnp.zeros((2, 8), jnp.int32), table[:1]]), kp, vp, **kw)
    for pool, was in ((kp4, before[0]), (vp4, before[1])):
        got = np.asarray(pool["ssm"])
        assert np.array_equal(got[:, others], was[:, others])
        assert not np.array_equal(got[:, 3], was[:, 3])


# -- the cache spec ------------------------------------------------------------------


def test_cache_spec_states_the_recurrent_kind():
    kv, ssm = cache_spec(CFG)
    assert (kv.name, kv.per_slot, kv.bytes_per_slot) == ("kv", False, 0)
    assert kv.bytes_per_token == 2 * 2 * (2 * 8) * 2  # layers x K, V x kv x dh x bf16
    assert (ssm.name, ssm.per_slot, ssm.bytes_per_token) == ("ssm", True, 0)
    # a slot a layer: 32 x 16 float32 states + 3 x (32 + 2 x 2 x 16) bf16 inputs
    assert ssm.bytes_per_slot == 2 * (32 * 16 * 4 + 3 * 96 * 2)
    assert kv_bytes_per_token(CFG) == kv.bytes_per_token
    kp, vp = init_page_pool(CFG, 9, PAGE, slots=3)
    assert kp["ssm"].shape == (2, 4, 2, 16, 16) and kp["ssm"].dtype == jnp.float32
    assert vp["ssm"].shape == (2, 4, 3, 96) and vp["ssm"].dtype == jnp.bfloat16
    # heads of 8, narrower than 128 lanes: a token's side by side
    assert kp["kv"].shape == vp["kv"].shape == (2, 9, PAGE, 2 * 8)
    # a model without a mixer keeps its two arrays
    plain = dec.DecoderConfig(vocab_size=128, dim=32, layers=2, heads=4, kv_heads=2, ffn=64)
    assert [p.name for p in cache_spec(plain)] == ["kv"]
    assert not isinstance(init_page_pool(plain, 9, PAGE)[0], dict)


def test_serve_dtypes_keep_the_recurrences_leaves_float32(params):
    dtypes = dec.serve_dtypes(CFG)
    assert jax.tree_util.tree_structure(dtypes) == jax.tree_util.tree_structure(params)
    layer = dtypes["layers"]
    for name in ("ssm_A_log", "ssm_D", "ssm_dt_bias"):
        assert layer[name] == jnp.float32
    assert layer["ssm_norm"]["scale"] == layer["attn_norm"]["scale"] == jnp.float32
    assert layer["ssm_in"]["w"] == layer["ssm_conv"]["w"] == jnp.bfloat16
    placed = jax.tree_util.tree_map(lambda a, dt: a.astype(dt), params, dtypes)
    assert ref.stated_float32_leaves_differ(placed, params) == 0
    bad = {**placed, "layers": {**placed["layers"], "ssm_A_log":
                                placed["layers"]["ssm_A_log"].astype(jnp.bfloat16)}}
    assert ref.stated_float32_leaves_differ(bad, params) == 4


# -- the server ----------------------------------------------------------------------


def _proc(model_config=None, **extra):
    cfg = {"type": "tpu_generate", "model": "decoder_lm",
           "model_config": model_config or TINY, "serving": "continuous",
           "max_input": 40, "max_new_tokens": 6, "slots": 2, "page_size": PAGE,
           "seq_buckets": [16], "prefill_chunk": 16, "eos_id": -1,
           "decode_kernel": "gather", "seed": 3, **extra}
    return build_component("processor", cfg, Resource())


def _counter(name, **labels):
    return global_registry().counter(name, labels={"model": "decoder_lm", **labels})


def test_a_reused_slot_serves_what_a_fresh_server_serves():
    """Five requests through two slots: every slot is handed on at least
    once, with its earlier tenant's state still in the pool. Each request's
    tokens equal those of a server that serves it alone, first. The host's
    counters equal a hand count."""
    prompts = [IDS[0, :21].tolist(), IDS[1, :13].tolist(), IDS[0, 5:38].tolist(),
               IDS[1, 2:9].tolist(), IDS[0, 10:27].tolist()]
    names = ("arkflow_gen_ssm_tokens_total", "arkflow_gen_ssm_masked_total")
    before = {(n, k): _counter(n, kind=k).value for n in names
              for k in ("decode", "chunk")}
    resets = global_registry().counter("arkflow_gen_ssm_state_resets_total",
                                       labels={"model": "decoder_lm"})
    resets0 = resets.value
    server = _proc()._server

    async def all_at_once():
        return await asyncio.gather(*[server.generate(p, 6) for p in prompts])

    shared = asyncio.run(all_at_once())
    assert server.m_uploads["chunk"].value > 0  # every prompt went in chunks
    chunks = sum(-(-len(p) // 16) for p in prompts)
    valid = _counter(names[0], kind="chunk").value - before[names[0], "chunk"]
    masked = _counter(names[1], kind="chunk").value - before[names[1], "chunk"]
    assert valid == sum(len(p) for p in prompts)
    assert valid + masked == 16 * chunks
    assert resets.value - resets0 == len(prompts)
    # every request's first token comes with its prompt's last chunk
    decoded = _counter(names[0], kind="decode").value - before[names[0], "decode"]
    assert decoded == len(prompts) * (6 - 1)
    for prompt, got in zip(prompts, shared):
        alone = asyncio.run(_proc()._server.generate(prompt, 6))
        assert got == alone and len(got) == 6


def _served_through_two_slots(server, n_new=24):
    """Five prompts through two slots, so each slot's last tenant inherited
    it. Returns what the judge reads: {prompt: [tokens]} as written."""
    prompts = [IDS[0, :21].tolist(), IDS[1, :13].tolist(), IDS[0, 5:38].tolist(),
               IDS[1, 2:9].tolist(), IDS[0, 10:27].tolist()]

    async def all_at_once():
        return await asyncio.gather(*[server.generate(p, n_new) for p in prompts])

    return {tuple(p): [t] for p, t in zip(prompts, asyncio.run(all_at_once()))}


def _judge_slots(proc, written, n_new=24) -> dict:
    rows, why = ref.last_tenants(proc._server, range(2), written, n_new)
    assert why is None, why
    assert min(r["tenancy"] for r in rows) >= 2
    return ref.judge_rows(proc.host_params, ref.hyper(proc.cfg),
                          [r["prompt"] for r in rows], [r["tokens"] for r in rows],
                          64, [r["state"] for r in rows])


def test_the_judge_reads_the_states_the_run_left_in_the_pool():
    """Rule (d): after a run, each slot's row of the state pool holds its
    LAST tenant's state — after the prompt and all but the last token,
    through a reset, padded chunks, the chunk / decode seam and decode steps
    beside a live neighbour — within the limit of the recurrence's."""
    proc = _proc(max_new_tokens=24)
    server = proc._server
    with pytest.raises(ConfigError, match="no recurrent state"):
        _proc(model_config={k: v for k, v in TINY.items() if not k.startswith(
            ("mamba", "ssm"))})._server.slot_state(0)
    assert server.slot_state(0)["prompt"] is None
    written = _served_through_two_slots(server)
    verdict = _judge_slots(proc, written)
    assert verdict["state_rel_err"] <= ref.STATE_REL_ERR, verdict
    assert verdict["state_updates_least"] >= 7 + 23
    # a tenant whose tokens are not what was written is a fault of its own
    st = server.slot_state(1)
    other = {**written, tuple(st["prompt"]): [st["tokens"][::-1]]}
    assert "not a row that was written" in ref.last_tenants(server, [1], other, 24)[1]


@pytest.mark.parametrize("fault", ["held_in_bfloat16", "rows_swapped"])
def test_the_judges_state_rule_refuses(fault):
    """A state rounded to bfloat16 at every decode step (the lower precision)
    and a state that is another slot's (a row-index fault) both read far
    over the limit, whatever their tokens say."""
    sound = _proc(max_new_tokens=24)
    sound = _judge_slots(sound, _served_through_two_slots(sound._server))
    proc = _proc(max_new_tokens=24)
    server = proc._server
    if fault == "held_in_bfloat16":
        real = server._decode

        def rounded(packed, kp, vp, *dev):
            out = real(packed, kp, vp, *dev)
            held = out[1]["ssm"].astype(jnp.bfloat16).astype(jnp.float32)
            return (out[0], {**out[1], "ssm": held}, *out[2:])

        server._decode = rounded
    written = _served_through_two_slots(server)
    if fault == "rows_swapped":
        pool = server.k_pages["ssm"]
        server.k_pages = {**server.k_pages,
                          "ssm": pool.at[:, 1].set(pool[:, 2]).at[:, 2].set(pool[:, 1])}
    verdict = _judge_slots(proc, written)
    assert sound["ok"] and not verdict["ok"], (sound, verdict)
    assert verdict["state_rel_err"] > max(5 * sound["state_rel_err"],
                                          ref.STATE_REL_ERR), (sound, verdict)


def test_swap_params_and_a_reset_rebuild_the_state_pool():
    """A flip of the params and a reset after an incident start from fresh
    pools, the state pool among them; the server serves as before."""
    server = _proc()._server
    prompt = IDS[0, :21].tolist()
    first = asyncio.run(server.generate(prompt, 6))
    assert float(jnp.abs(server.k_pages["ssm"]).max()) > 0

    async def flip():
        await server.swap_params(server.params)
        clean = float(jnp.abs(server.k_pages["ssm"]).max())
        return clean, await server.generate(prompt, 6)

    clean, again = asyncio.run(flip())
    assert clean == 0.0 and again == first
    server._reset_device_state()
    assert float(jnp.abs(server.k_pages["ssm"]).max()) == 0.0
    assert float(jnp.abs(server.v_pages["ssm"].astype(jnp.float32)).max()) == 0.0


def test_the_servers_gauge_counts_busy_slots():
    server = _proc()._server
    gauges = {holder: (gauge, unit) for gauge, holder, unit in server.m_kv_live}
    assert set(gauges) == {"pages", "slots"}
    assert gauges["slots"][1] == cache_spec(CFG)[1].bytes_per_slot
    server._update_gauges(2)
    assert gauges["slots"][0].value == 2 * cache_spec(CFG)[1].bytes_per_slot


def test_kernel_parity_probe_holds_the_mixers_kernels():
    """The build-time probe runs the hybrid prefill, decode step and chunk
    through both kernel sets; a state update that is wrong fails the build."""
    proc = _proc(decode_kernel="paged", kernel_interpret=True)
    assert proc._server.kernel_parity["ok"], proc._server.kernel_parity
    real = ss.ssm_state_update
    jax.clear_caches()  # the probe's jitted steps were traced with the real one
    try:
        ss.ssm_state_update = lambda *a, kernel=False, **kw: (
            lambda y, pool: (y * (50.0 if kernel else 1.0), pool))(
                *real(*a, kernel=kernel, **kw))
        with pytest.raises(ConfigError, match="disagrees"):
            _proc(decode_kernel="paged", kernel_interpret=True)
    finally:
        ss.ssm_state_update = real
        jax.clear_caches()


@pytest.mark.parametrize("extra,needle", [
    ({"prefix_cache_pages": 8}, "aliased pages skip"),
    ({"speculative_tokens": 2}, "rejected draft"),
    ({"dispatch_depth": 3}, "dispatch_depth > 2"),
    ({"mesh": {"tp": 2}}, "one chip"),
    ({"serving": "batch"}, "serving: continuous"),
    ({"prefill_chunk": 0}, "prefills in chunks"),
])
def test_hybrid_model_refuses_what_cannot_carry_a_state(extra, needle):
    with pytest.raises(ConfigError, match=needle):
        _proc(**extra)


def test_hybrid_model_refuses_kv_push():
    proc = _proc()
    assert getattr(proc, "disagg", None) is None
    with pytest.raises(ConfigError, match="no wire form"):
        asyncio.run(proc._server.prefill_export([1, 2, 3], 2))
    with pytest.raises(ConfigError, match="no wire form"):
        asyncio.run(proc._server.generate_from_pages({"done": False}))


@pytest.mark.parametrize("bad", [
    {"kv_lora_rank": 32, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 8},
    {"num_experts": 4}, {"use_ring_attention": True},
    {"mamba_n_heads": 3}, {"mamba_d_state": 0}, {"mamba_n_groups": 3},
    {"ssm_multipliers": [1.0, 1.0]}, {"mlp_multipliers": [1.0]},
    {"head_dim": 7}, {"mamba_d_ssm": 0},
], ids=lambda bad: "-".join(bad))
def test_hybrid_config_values_that_do_not_compose_raise(bad):
    with pytest.raises(ConfigError):
        dec.DecoderConfig(**{**TINY, **bad})


def test_paths_without_a_state_refuse_the_hybrid_block():
    with pytest.raises(ConfigError, match="recurrent state"):
        dec.init_kv_cache(CFG, 1, 16)
    # the mesh itself is refused where one is built (the server, the processor)
    assert dec.param_specs(CFG, {})["layers"]["ssm_A_log"] is not None
