"""Paged KV cache + continuous-batching serving tests (tiny shapes, CPU).

Correctness bar: paged decode must produce exactly the tokens the
contiguous-cache path produces (greedy decode is deterministic), through
page-table indirection, slot reuse, and mid-flight admission.
"""

from __future__ import annotations

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arkflow_tpu.errors import ConfigError
from arkflow_tpu.models import get_model
from arkflow_tpu.models.paged_decode import (
    init_page_pool,
    paged_decode_step,
    paged_prefill,
)
from arkflow_tpu.tpu.serving import GenerationServer

TINY = dict(vocab_size=128, dim=64, layers=2, heads=4, kv_heads=2, ffn=96, max_seq=64)
TINY_MOE = dict(vocab_size=128, dim=32, layers=2, heads=2, kv_heads=1, ffn=48,
                max_seq=64, num_experts=4)


def _reference_generate(fam, params, cfg, prompt: list[int], max_new: int,
                        eos_id: int = 2) -> list[int]:
    ids = jnp.asarray([prompt], jnp.int32)
    lengths = jnp.asarray([len(prompt)], jnp.int32)
    tokens, counts = fam.extras["generate"](
        params, cfg, ids, lengths, max_new_tokens=max_new, eos_id=eos_id)
    return np.asarray(tokens)[0, : int(counts[0])].tolist()


def test_paged_decode_matches_contiguous():
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(0), cfg)
    prompt = [3, 17, 42, 7, 91]
    n = len(prompt)

    # contiguous reference: prefill + 6 decode steps
    ex = fam.extras
    cache = ex["init_kv_cache"](cfg, 1, 32)
    nxt_ref, cache = ex["prefill"](params, cfg, jnp.asarray([prompt], jnp.int32), cache)
    ref = [int(nxt_ref[0])]
    for _ in range(5):
        nxt_ref, cache = ex["decode_step"](
            params, cfg, jnp.asarray([[ref[-1]]], jnp.int32), cache)
        ref.append(int(nxt_ref[0]))

    # paged path: page_size 4 -> prompt spans 2 pages, decode crosses a
    # page boundary mid-run
    kp, vp = init_page_pool(cfg, num_pages=9, page_size=4)
    table = jnp.asarray([[5, 2, 7, 0, 0, 0, 0, 0]], jnp.int32)  # scattered pages
    ids = np.zeros((1, 8), np.int32)
    ids[0, :n] = prompt
    nxt, kp, vp = paged_prefill(
        params, cfg, jnp.asarray(ids), jnp.asarray([n], jnp.int32), table, kp, vp)
    got = [int(nxt[0])]
    lengths = np.array([n], np.int32)
    for _ in range(5):
        nxt, kp, vp = paged_decode_step(
            params, cfg, jnp.asarray([got[-1]], jnp.int32),
            jnp.asarray(lengths), jnp.asarray([True]), table, kp, vp)
        lengths += 1
        got.append(int(nxt[0]))
    assert got == ref


def test_paged_decode_isolates_slots():
    """Garbage in one slot's pages must not affect another slot (mask +
    page-table isolation)."""
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(1), cfg)
    prompt = [9, 4, 55]
    kp, vp = init_page_pool(cfg, num_pages=8, page_size=4)
    # slot 1's pages are pre-polluted with noise
    kp = kp.at[:, 6].set(jnp.ones_like(kp[:, 6]) * 7.0)
    vp = vp.at[:, 6].set(jnp.ones_like(vp[:, 6]) * -3.0)
    table = jnp.asarray([[2, 3], [6, 6]], jnp.int32)
    ids = np.zeros((2, 4), np.int32)
    ids[0, : len(prompt)] = prompt
    ids[1, :] = [1, 2, 3, 4]
    nxt, kp, vp = paged_prefill(
        params, cfg, jnp.asarray(ids), jnp.asarray([3, 4], jnp.int32), table, kp, vp)
    # single-slot reference for slot 0
    kp2, vp2 = init_page_pool(cfg, num_pages=8, page_size=4)
    ids0 = np.zeros((1, 4), np.int32)
    ids0[0, : len(prompt)] = prompt
    nxt0, _, _ = paged_prefill(
        params, cfg, jnp.asarray(ids0), jnp.asarray([3], jnp.int32),
        jnp.asarray([[2, 3]], jnp.int32), kp2, vp2)
    assert int(nxt[0]) == int(nxt0[0])


def test_moe_incremental_decode_matches_forward():
    """MoE decoders now decode incrementally; the cache path must agree with
    the full forward pass."""
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY_MOE)
    params = fam.init(jax.random.PRNGKey(2), cfg)
    ex = fam.extras
    seq = [3, 17, 42, 7]
    full_logits = ex["forward"](params, cfg, jnp.asarray([seq], jnp.int32))
    cache = ex["init_kv_cache"](cfg, 1, 16)
    nxt, cache = ex["prefill"](params, cfg, jnp.asarray([seq], jnp.int32), cache)
    assert int(nxt[0]) == int(jnp.argmax(full_logits[0, -1]))
    # and whole-generation jit works for MoE
    out = _reference_generate(fam, params, cfg, seq, max_new=4)
    assert len(out) <= 4


def test_generation_server_matches_reference_and_reuses_pages():
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(3), cfg)
    prompts = [[3, 17, 42], [9], [55, 1, 2, 8, 13], [7, 7], [100, 12, 44, 2]]
    refs = [_reference_generate(fam, params, cfg, p, max_new=6) for p in prompts]

    async def go():
        server = GenerationServer(params, cfg, slots=2, page_size=4, max_seq=32)
        free0 = len(server._free_pages)
        # the arkflow_gen_* series are registry-global and unlabeled: other
        # tests' servers share them, so token accounting asserts the DELTA
        tok0 = server.m_tokens.value
        # 5 overlapping requests through 2 slots: admission + slot reuse
        outs = await asyncio.gather(*[
            server.generate(p, max_new_tokens=6) for p in prompts])
        await server.close()
        assert outs == refs
        assert len(server._free_pages) == free0  # every page returned
        assert server.m_tokens.value - tok0 == sum(len(r) for r in refs)

    asyncio.run(go())


def test_chunked_prefill_matches_one_shot_kernel():
    """paged_prefill_chunk over 3 chunks must reproduce one-shot
    paged_prefill exactly: same next token, same cached K/V (checked by
    continuing greedy decode from both caches)."""
    from arkflow_tpu.models.paged_decode import paged_prefill_chunk

    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(4), cfg)
    prompt = [3, 17, 42, 7, 91, 5, 66, 23, 11, 2, 81, 30]  # 12 tokens
    n = len(prompt)
    table = jnp.asarray([[5, 2, 7, 1, 0, 0, 0, 0]], jnp.int32)

    def decode_5(kp, vp, first):
        got = [int(first)]
        lengths = np.array([n], np.int32)
        for _ in range(5):
            nxt, kp, vp = paged_decode_step(
                params, cfg, jnp.asarray([got[-1]], jnp.int32),
                jnp.asarray(lengths), jnp.asarray([True]), table, kp, vp)
            lengths += 1
            got.append(int(nxt[0]))
        return got

    # one-shot
    kp, vp = init_page_pool(cfg, num_pages=9, page_size=4)
    ids = np.zeros((1, 16), np.int32)
    ids[0, :n] = prompt
    nxt, kp, vp = paged_prefill(
        params, cfg, jnp.asarray(ids), jnp.asarray([n], jnp.int32), table, kp, vp)
    ref = decode_5(kp, vp, int(nxt[0]))

    # chunked: 5 + 5 + 2 (final chunk partial)
    kp2, vp2 = init_page_pool(cfg, num_pages=9, page_size=4)
    c = 5
    logits = None
    for off in range(0, n, c):
        chunk = prompt[off:off + c]
        cids = np.zeros((1, c), np.int32)
        cids[0, :len(chunk)] = chunk
        logits, kp2, vp2 = paged_prefill_chunk(
            params, cfg, jnp.asarray(cids), jnp.asarray([off], jnp.int32),
            jnp.asarray([len(chunk)], jnp.int32), table, kp2, vp2)
    first = int(jnp.argmax(logits[0]))
    got = decode_5(kp2, vp2, first)
    assert got == ref


def test_generation_server_chunked_prefill_matches_one_shot():
    """Server with prefill_chunk must emit exactly the one-shot outputs,
    with long and short prompts in flight together (interleaved admission)."""
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(5), cfg)
    prompts = [list(range(3, 25)),    # 22 tokens -> 6 chunks of 4
               [9, 4],                # short: admits one-shot
               list(range(40, 55)),   # 15 tokens -> chunked, partial tail
               [7]]
    refs = [_reference_generate(fam, params, cfg, p, max_new=5) for p in prompts]

    async def go():
        server = GenerationServer(params, cfg, slots=2, page_size=4,
                                  max_seq=32, prefill_chunk=4)
        free0 = len(server._free_pages)
        outs = await asyncio.gather(*[
            server.generate(p, max_new_tokens=5) for p in prompts])
        await server.close()
        assert outs == refs
        assert len(server._free_pages) == free0
        assert not server._prefill_pos

    asyncio.run(go())


def test_chunked_prefill_goes_to_the_slot_admitted_first():
    """Two slots. A short prompt (slot 0) and a long one (slot 1, three more
    chunks to go) are admitted; the short one finishes and a third request
    takes slot 0 while the long one is still prefilling: the long prompt's
    chunks come first — first admitted, first prefilled, whatever the slot —
    and the outputs are what they were."""
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(0), cfg)
    prompts = [[5, 6, 7], list(range(10, 36)), [40, 41, 42, 43, 44, 45, 46, 47, 48]]

    async def go():
        server = GenerationServer(params, cfg, slots=2, page_size=4,
                                  max_seq=64, prefill_chunk=8)
        step, decode, order = server._prefill_step, server._step, []

        async def recording(slot, kind="chunk"):
            order.append(prompts.index(server._slot_req[slot].prompt))
            await step(slot, kind)

        async def riding_too(active, riding=-1):
            # a chunk that rides a decode step is the same prompt's turn
            if riding >= 0:
                order.append(prompts.index(server._slot_req[riding].prompt))
            await decode(active, riding)

        server._prefill_step, server._step = recording, riding_too
        outs = await asyncio.gather(*[
            server.generate(p, n) for p, n in zip(prompts, (2, 4, 4))])
        await server.close()
        return outs, order

    outs, order = asyncio.run(go())
    assert order.count(1) == 4 and order.count(2) == 2
    # the third request's chunks start only after the long prompt's last
    assert max(i for i, r in enumerate(order) if r == 1) < order.index(2)
    for prompt, out in zip(prompts, outs):
        assert out == _reference_generate(fam, params, cfg, prompt, len(out))


def test_speculative_decode_matches_greedy_exactly():
    """Speculative verify (n-gram drafts) must reproduce exact greedy
    outputs for repetitive AND non-repetitive prompts, and actually accept
    drafts on the repetitive one."""
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(6), cfg)
    prompts = [[5, 9] * 8,                 # strongly repetitive: drafts hit
               [3, 17, 42, 7, 91],         # arbitrary
               [11]]                       # minimal history
    refs = [_reference_generate(fam, params, cfg, p, max_new=8) for p in prompts]

    async def go():
        server = GenerationServer(params, cfg, slots=2, page_size=4,
                                  max_seq=40, speculative_tokens=3)
        free0 = len(server._free_pages)
        outs = await asyncio.gather(*[
            server.generate(p, max_new_tokens=8) for p in prompts])
        await server.close()
        assert outs == refs
        assert len(server._free_pages) == free0
        assert server.m_spec_drafted.value > 0
        # fewer verify steps than tokens emitted == speculation paid off
        assert server.m_steps.value < server.m_tokens.value

    asyncio.run(go())


def test_speculative_with_sampling_rejected():
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(7), cfg)
    with pytest.raises(ConfigError, match="greedy"):
        GenerationServer(params, cfg, slots=2, page_size=4, max_seq=32,
                         speculative_tokens=2, temperature=0.8)


def test_speculative_composes_with_chunked_prefill():
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(8), cfg)
    prompt = [4, 6] * 9  # 18 tokens, repetitive
    ref = _reference_generate(fam, params, cfg, prompt, max_new=6)

    async def go():
        server = GenerationServer(params, cfg, slots=2, page_size=4,
                                  max_seq=40, prefill_chunk=4,
                                  speculative_tokens=3)
        out = await server.generate(prompt, max_new_tokens=6)
        await server.close()
        assert out == ref

    asyncio.run(go())


def test_prefix_cache_reuses_pages_and_stays_exact():
    """A second request sharing the first's prompt prefix must alias the
    cached pages (fewer fresh prefill tokens) and still emit exactly the
    reference greedy output."""
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(9), cfg)
    common = list(range(3, 3 + 12))  # 12 tokens = 3 full pages of 4
    p1 = common + [60, 61]
    p2 = common + [70, 71, 72]  # same 3-page prefix, different tail
    refs = [_reference_generate(fam, params, cfg, p, max_new=5) for p in (p1, p2)]

    async def go():
        server = GenerationServer(params, cfg, slots=2, page_size=4,
                                  max_seq=32, prefix_cache_pages=8)
        hits0 = server.m_prefix_hits.value  # registry counters are global
        pages0 = server.m_prefix_pages.value
        out1 = await server.generate(p1, max_new_tokens=5)
        assert server.m_prefix_hits.value == hits0  # cold cache
        out2 = await server.generate(p2, max_new_tokens=5)
        await server.close()
        assert [out1, out2] == refs
        assert server.m_prefix_hits.value == hits0 + 1
        assert server.m_prefix_pages.value == pages0 + 3  # the full-page prefix
        # cache still holds refs; every non-cached page was returned
        assert server._cache_held > 0
        assert all(c > 0 for c in server._page_refs.values())

    asyncio.run(go())


def test_prefix_cache_eviction_frees_pages():
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(10), cfg)

    async def go():
        # cache capped at 2 pages -> inserting a 3-page prefix evicts to fit,
        # and distinct prompts rotate the LRU
        server = GenerationServer(params, cfg, slots=2, page_size=4,
                                  max_seq=32, prefix_cache_pages=2)
        total_pages = server.num_pages - 1
        for base in (0, 30, 60):
            await server.generate(list(range(base + 1, base + 10)), max_new_tokens=3)
        await server.close()
        assert server._cache_held <= 2
        # pages referenced only by the cache + free pages == whole pool
        held = sum(len(v) for v in server._prefix_cache.values())
        assert held == server._cache_held
        assert len(server._free_pages) + held == total_pages

    asyncio.run(go())


def test_prefix_cache_composes_with_speculation_and_chunks():
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(11), cfg)
    common = [5, 9] * 6
    p1 = common + [33]
    p2 = common + [44, 45]
    refs = [_reference_generate(fam, params, cfg, p, max_new=6) for p in (p1, p2)]

    async def go():
        server = GenerationServer(params, cfg, slots=2, page_size=4,
                                  max_seq=40, prefix_cache_pages=8,
                                  prefill_chunk=4, speculative_tokens=3)
        hits0 = server.m_prefix_hits.value
        out1 = await server.generate(p1, max_new_tokens=6)
        out2 = await server.generate(p2, max_new_tokens=6)
        await server.close()
        assert [out1, out2] == refs
        assert server.m_prefix_hits.value >= hits0 + 1

    asyncio.run(go())


def test_prefix_cache_counts_distinct_pages_for_nested_prefixes():
    """A short prefix nested inside a longer cached prefix shares pages;
    capacity accounting must count physical pages once."""
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(13), cfg)
    common = list(range(3, 3 + 8))  # 2 full pages of 4

    async def go():
        server = GenerationServer(params, cfg, slots=2, page_size=4,
                                  max_seq=32, prefix_cache_pages=8)
        # first request caches [p0, p1]; second shares them and extends to
        # 3 full pages -> caches [p0, p1, p2] as a distinct (longer) entry
        await server.generate(common + [50], max_new_tokens=3)
        await server.generate(common + [51, 52, 53, 54, 55], max_new_tokens=3)
        await server.close()
        entries = sum(len(v) for v in server._prefix_cache.values())
        assert len(server._prefix_cache) == 2
        assert entries == 5          # 2 + 3 entry-held pages
        assert server._cache_held == 3  # but only 3 DISTINCT pages

    asyncio.run(go())


def test_serve_loop_crash_returns_pages():
    """A serve-loop crash fails in-flight futures AND returns their pages —
    repeated crashes must not shrink the pool."""
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(12), cfg)

    async def go():
        server = GenerationServer(params, cfg, slots=2, page_size=4, max_seq=32)
        total = server.num_pages - 1

        def boom(*a, **k):
            raise RuntimeError("injected device failure")

        server._decode = boom
        with pytest.raises(RuntimeError):
            await server.generate([3, 4, 5], max_new_tokens=4)
        assert len(server._free_pages) == total
        assert not server._page_refs

    asyncio.run(go())


def test_generation_server_validates():
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(4), cfg)

    async def go():
        server = GenerationServer(params, cfg, slots=1, page_size=4, max_seq=16)
        with pytest.raises(ConfigError):
            await server.generate(list(range(20)), max_new_tokens=8)
        assert await server.generate([], max_new_tokens=4) == []
        await server.close()

    asyncio.run(go())


def test_tpu_generate_continuous_processor():
    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded

    ensure_plugins_loaded()
    proc = build_component(
        "processor",
        {"type": "tpu_generate", "model": "decoder_lm",
         "model_config": TINY, "serving": "continuous",
         "slots": 2, "page_size": 4, "max_input": 16, "max_new_tokens": 5,
         "batch_buckets": [4], "seq_buckets": [16]},
        Resource(),
    )

    async def go():
        batch = MessageBatch.new_binary([b"sensor alpha", b"sensor beta", b"x"])
        out = (await proc.process(batch))[0]
        col = out.column("generated").to_pylist()
        assert len(col) == 3 and all(isinstance(t, str) for t in col)
        await proc._server.close()

    asyncio.run(go())


def test_tpu_generate_serving_validation():
    from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded

    ensure_plugins_loaded()
    with pytest.raises(ConfigError):
        build_component(
            "processor",
            {"type": "tpu_generate", "model": "decoder_lm",
             "model_config": TINY, "serving": "bogus"},
            Resource(),
        )


def test_page_starvation_finishes_longest_without_corruption():
    """When the pool runs dry, the longest sequence ends early and the
    survivor's tokens stay EXACTLY the reference sequence (no scratch-page
    corruption of its context)."""
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(3), cfg)
    p1, p2 = [3, 17, 42, 7, 91, 12, 8, 2], [9, 4, 55, 1, 2, 3, 4, 5]
    ref2 = _reference_generate(fam, params, cfg, p2, max_new=20, eos_id=-1)

    async def go():
        # 10 pages: both 8-token prompts fit (3 pages each) but cannot both
        # grow to 28 tokens (7 pages each) -> starvation mid-flight
        server = GenerationServer(params, cfg, slots=2, page_size=4,
                                  max_seq=32, num_pages=10, eos_id=-1)
        r1, r2 = await asyncio.gather(
            server.generate(p1, max_new_tokens=20),
            server.generate(p2, max_new_tokens=20))
        await server.close()
        # one of them was cut short to free pages; the other ran to 20 and
        # must match the solo reference exactly
        assert (len(r1) == 20) != (len(r2) == 20) or (r1 and r2)
        if len(r2) == 20:
            assert r2 == ref2
        else:
            assert r2 == ref2[: len(r2)]

    asyncio.run(go())


def test_close_mid_flight_fails_futures_instead_of_hanging():
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(5), cfg)

    async def go():
        server = GenerationServer(params, cfg, slots=1, page_size=4, max_seq=64)
        task = asyncio.create_task(
            server.generate([5, 6, 7], max_new_tokens=500 // 10))
        await asyncio.sleep(0.2)  # let it admit and start decoding
        await server.close()
        with pytest.raises(ConfigError, match="closed"):
            await asyncio.wait_for(task, 5)

    asyncio.run(go())


def test_sampling_temperature_and_topk():
    """temperature=0 is greedy; sampling is deterministic per key, varies
    across keys, and top_k=1 collapses back to greedy."""
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(7), cfg)
    ex = fam.extras
    prompt = jnp.asarray([[3, 17, 42]], jnp.int32)
    lens = jnp.asarray([3], jnp.int32)

    greedy, _ = ex["generate"](params, cfg, prompt, lens, max_new_tokens=8,
                               eos_id=-1)
    g2, _ = ex["generate"](params, cfg, prompt, lens, max_new_tokens=8,
                           eos_id=-1, temperature=0.0,
                           rng_key=jax.random.PRNGKey(1))
    assert np.array_equal(np.asarray(greedy), np.asarray(g2))

    k1, _ = ex["generate"](params, cfg, prompt, lens, max_new_tokens=8,
                           eos_id=-1, temperature=1.5,
                           rng_key=jax.random.PRNGKey(1))
    k1b, _ = ex["generate"](params, cfg, prompt, lens, max_new_tokens=8,
                            eos_id=-1, temperature=1.5,
                            rng_key=jax.random.PRNGKey(1))
    assert np.array_equal(np.asarray(k1), np.asarray(k1b))  # per-key determinism
    draws = [np.asarray(ex["generate"](params, cfg, prompt, lens,
                                       max_new_tokens=8, eos_id=-1,
                                       temperature=1.5,
                                       rng_key=jax.random.PRNGKey(k))[0])
             for k in range(5)]
    assert any(not np.array_equal(draws[0], d) for d in draws[1:])

    topk1, _ = ex["generate"](params, cfg, prompt, lens, max_new_tokens=8,
                              eos_id=-1, temperature=0.7, top_k=1,
                              rng_key=jax.random.PRNGKey(3))
    assert np.array_equal(np.asarray(topk1), np.asarray(greedy))


def test_continuous_server_sampling_deterministic_per_seed():
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(8), cfg)

    async def run(seed):
        server = GenerationServer(params, cfg, slots=2, page_size=4, max_seq=32,
                                  temperature=1.2, top_k=8, seed=seed)
        out = await server.generate([5, 9, 2], max_new_tokens=6)
        await server.close()
        return out

    a = asyncio.run(run(42))
    b = asyncio.run(run(42))
    assert a == b
    assert len(a) == 6
    # the seed must actually steer sampling: some seed in a small set differs
    others = [asyncio.run(run(seed)) for seed in (43, 44, 45, 46)]
    assert any(o != a for o in others)


def test_tpu_generate_tensor_parallel_batch_mode():
    """tp=2 sharded generation must match single-device greedy output."""
    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded

    ensure_plugins_loaded()
    devs = jax.devices("cpu")
    if len(devs) < 2:
        pytest.skip("needs 2 virtual devices")
    base = {"type": "tpu_generate", "model": "decoder_lm",
            "model_config": TINY, "max_input": 16, "max_new_tokens": 6,
            "eos_id": -1, "batch_buckets": [4], "seq_buckets": [16]}
    single = build_component("processor", base, Resource())
    tp = build_component("processor", {**base, "mesh": {"tp": 2}}, Resource())

    async def go():
        batch = MessageBatch.new_binary([b"alpha beta", b"gamma"])
        a = (await single.process(batch))[0].column("generated").to_pylist()
        b = (await tp.process(batch))[0].column("generated").to_pylist()
        assert a == b

    asyncio.run(go())


def test_tpu_generate_continuous_plus_dp_mesh_rejected():
    """Continuous serving composes with tp now; dp/sp batch-splitting still
    doesn't (the lockstep slot grid is global) and must fail clearly."""
    from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded

    ensure_plugins_loaded()
    for axis in ("dp", "sp"):
        with pytest.raises(ConfigError, match="batch-split"):
            build_component(
                "processor",
                {"type": "tpu_generate", "model": "decoder_lm", "model_config": TINY,
                 "serving": "continuous", "mesh": {axis: 2}},
                Resource(),
            )


# -- tensor-parallel continuous serving (sharded page pools over tp) --------
#
# Runs on the virtual CPU mesh conftest pins. Parity is asserted against the
# SINGLE-CHIP continuous server on fixed prompts/seed: tensor-parallel
# matmuls psum over the contraction dim (wo / w_down), so logits differ in
# the last bits and a near-tied argmax could legitimately flip — the fixed
# prompt set below is tie-free under this seed, and XLA CPU is deterministic,
# so the assertions are exact and stable (same convention as the tp=2 batch
# generation test above).

TP_PROMPTS = [[9], [55, 1, 2, 8, 13], [9, 4], [2, 77, 31, 5], [60, 61, 62]]


def _tp_mesh(n=2):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} virtual devices")
    from arkflow_tpu.parallel.mesh import MeshSpec, create_mesh

    return create_mesh(MeshSpec(tp=n), devices=devs[:n])


def _tp_setup(seed=3):
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(seed), cfg)
    mesh = _tp_mesh()
    from arkflow_tpu.parallel.mesh import shard_params

    axes = {name: name for name in mesh.axis_names}
    sharded = shard_params(params, fam.param_specs(cfg, axes), mesh)
    return cfg, params, sharded, mesh


def _serve(params, cfg, prompts, max_new, mesh=None, **kw):
    async def go():
        server = GenerationServer(params, cfg, slots=2, page_size=4,
                                  max_seq=40, mesh=mesh, **kw)
        free0 = len(server._free_pages)
        outs = await asyncio.gather(*[
            server.generate(p, max_new_tokens=max_new) for p in prompts])
        await server.close()
        assert len(server._free_pages) == free0  # every page returned
        return outs, server

    return asyncio.run(go())


def test_tp_server_parity_prefill_and_decode():
    """Sharded one-shot prefill + lockstep decode must emit exactly the
    single-chip continuous server's tokens (KV pages split over KV heads)."""
    cfg, params, sharded, mesh = _tp_setup()
    ref, _ = _serve(params, cfg, TP_PROMPTS, 6)
    got, server = _serve(sharded, cfg, TP_PROMPTS, 6, mesh=mesh)
    assert got == ref
    # the pools really are sharded: the tp axis carries 2 shards
    from arkflow_tpu.parallel.mesh import tp_size

    assert tp_size(server.mesh) == 2
    assert not server.k_pages.sharding.is_fully_replicated


def test_tp_server_parity_chunked_prefill():
    """Chunked prefill under tp: long prompts admit in fixed chunks through
    the sharded chunk kernel and still match the single-chip server."""
    cfg, params, sharded, mesh = _tp_setup()
    prompts = [list(range(3, 25)), [9, 4], list(range(40, 55)), [7]]
    ref, _ = _serve(params, cfg, prompts, 5, prefill_chunk=4)
    got, _ = _serve(sharded, cfg, prompts, 5, mesh=mesh, prefill_chunk=4)
    assert got == ref


def test_tp_server_parity_speculative_verify():
    """Self-drafted speculative verification under tp: the sharded verify
    step scores k positions and the accepted prefix matches single-chip —
    and drafts actually land (the repetitive prompt accepts)."""
    cfg, params, sharded, mesh = _tp_setup()
    prompts = [[5, 9] * 8, [11], [9, 4]]
    ref, _ = _serve(params, cfg, prompts, 8, speculative_tokens=3)
    got, server = _serve(sharded, cfg, prompts, 8, mesh=mesh,
                         speculative_tokens=3)
    assert got == ref
    assert server.m_spec_drafted.value > 0


def test_tp_prefix_cache_hits_under_sharded_pool():
    """Prefix-cache aliasing is pure host-side page bookkeeping — it must
    hit and stay exact when the pages it aliases are sharded over tp."""
    cfg, params, sharded, mesh = _tp_setup(seed=9)
    common = list(range(3, 3 + 12))  # 3 full pages of 4
    p1, p2 = common + [60, 61], common + [70, 71, 72]
    ref, _ = _serve(params, cfg, [p1], 5)
    ref2, _ = _serve(params, cfg, [p2], 5)

    async def go():
        server = GenerationServer(sharded, cfg, slots=2, page_size=4,
                                  max_seq=40, mesh=mesh, prefix_cache_pages=8)
        hits0 = server.m_prefix_hits.value
        out1 = await server.generate(p1, max_new_tokens=5)
        out2 = await server.generate(p2, max_new_tokens=5)
        await server.close()
        assert server.m_prefix_hits.value == hits0 + 1
        return out1, out2

    out1, out2 = asyncio.run(go())
    assert [out1] == ref and [out2] == ref2


def test_tp_kv_head_divisibility_and_dp_rejected():
    from arkflow_tpu.parallel.mesh import MeshSpec, create_mesh

    fam = get_model("decoder_lm")
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs 2 virtual devices")
    # kv_heads=3 does not divide tp=2
    cfg3 = fam.make_config(**{**TINY, "heads": 3, "kv_heads": 3, "dim": 66,
                              "ffn": 64})
    params3 = fam.init(jax.random.PRNGKey(0), cfg3)
    mesh = create_mesh(MeshSpec(tp=2), devices=devs[:2])
    with pytest.raises(ConfigError, match="kv_heads"):
        GenerationServer(params3, cfg3, slots=2, page_size=4, max_seq=16,
                         mesh=mesh)
    # dp batch-splitting does not compose with the lockstep slot grid
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(0), cfg)
    dp_mesh = create_mesh(MeshSpec(dp=2), devices=devs[:2])
    with pytest.raises(ConfigError, match="tensor-parallel only"):
        GenerationServer(params, cfg, slots=2, page_size=4, max_seq=16,
                         mesh=dp_mesh)


def test_tpu_generate_continuous_mesh_processor_end_to_end():
    """The processor path: serving continuous + mesh {tp: 2} builds, serves a
    batch, and matches the unsharded continuous processor's output text."""
    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded

    ensure_plugins_loaded()
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs 2 virtual devices")
    base = {"type": "tpu_generate", "model": "decoder_lm", "model_config": TINY,
            "serving": "continuous", "slots": 2, "page_size": 4,
            "max_input": 16, "max_new_tokens": 5,
            "batch_buckets": [4], "seq_buckets": [16]}
    single = build_component("processor", base, Resource())
    tp = build_component("processor", {**base, "mesh": {"tp": 2}}, Resource())

    async def go():
        batch = MessageBatch.new_binary([b"sensor alpha", b"sensor beta", b"x"])
        a = (await single.process(batch))[0].column("generated").to_pylist()
        b = (await tp.process(batch))[0].column("generated").to_pylist()
        await single._server.close()
        await tp._server.close()
        return a, b

    a, b = asyncio.run(go())
    assert a == b
    # the generate path now exposes its device runner like tpu_inference:
    # the engine's /health introspection and the fault plugin both use it
    rep = tp.runner.health_report()
    assert rep["serving"] == "continuous"
    assert rep["mesh"] == {"tp": 2}
    assert rep["state"] == "healthy"


# -- generate path on the shared serving core (deadlines / health / nack) ---


def test_generation_server_deadline_miss_marks_unhealthy_then_recovers():
    """A hung generate step trips the shared core's watchdog: in-flight
    requests fail (their batches nack upstream), the server goes UNHEALTHY,
    and the next request waits out the probe backoff, rebuilds the jitted
    steps on fresh pools, and serves exactly the reference output."""
    from arkflow_tpu.errors import StepDeadlineExceeded
    from arkflow_tpu.tpu.health import HealthConfig

    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(3), cfg)
    ref = _reference_generate(fam, params, cfg, [9, 4], max_new=4)

    async def go():
        server = GenerationServer(
            params, cfg, slots=2, page_size=4, max_seq=32,
            step_deadline_s=0.25, step_deadline_first_s=60.0,
            health_config=HealthConfig(probe_backoff_s=0.05))
        misses0 = server.core.m_deadline_miss.value
        rebuilds0 = server.core.m_rebuilds.value
        await server.generate([9, 4], max_new_tokens=4)  # warm the shapes
        server.inject_step_fault("hang", 3.0)
        with pytest.raises(StepDeadlineExceeded):
            await server.generate([9, 4], max_new_tokens=4)
        assert server.core.health.state == "unhealthy"
        assert server.core.m_deadline_miss.value == misses0 + 1
        # pools were reset: nothing leaked even though the zombie owned them
        assert len(server._free_pages) == server.num_pages - 1
        assert not server._page_refs
        # recovery probe: waits the backoff, rebuilds, serves the reference
        out = await server.generate([9, 4], max_new_tokens=4)
        assert out == ref
        assert server.core.health.state == "healthy"
        assert server.core.m_rebuilds.value >= rebuilds0 + 1
        await server.close()

    asyncio.run(go())


def test_generate_stream_deadline_miss_nacks_and_redelivery_heals():
    """ISSUE-9 acceptance: a deadline-missed generate step marks UNHEALTHY
    and NACKS — the fault input redelivers, the probe re-admits, zero loss."""
    from arkflow_tpu.config import StreamConfig
    from arkflow_tpu.runtime import build_stream

    cfg = StreamConfig.from_mapping({
        "name": "gen-deadline",
        "input": {
            "type": "fault",
            "redeliver_unacked": True,
            "inner": {"type": "memory", "messages": ["r0", "r1", "r2"]},
        },
        "pipeline": {
            "thread_num": 1,
            "max_delivery_attempts": 5,
            "processors": [
                {"type": "fault",
                 # call 2: call 1 compiles every step shape under the
                 # first-compile budget; the armed hang then trips the warm
                 # 250ms deadline on call 2's first device step
                 "faults": [{"kind": "hang", "at": 2, "duration": "3s"}],
                 "inner": {"type": "tpu_generate", "model": "decoder_lm",
                           "model_config": TINY, "serving": "continuous",
                           "slots": 2, "page_size": 4, "max_input": 16,
                           "max_new_tokens": 4, "eos_id": -1,
                           "batch_buckets": [4], "seq_buckets": [16],
                           "step_deadline": "250ms",
                           "step_deadline_first": "60s",
                           "health": {"probe_backoff": "50ms"}}},
            ],
        },
        "output": {"type": "drop"},
    })
    stream = build_stream(cfg)
    server = stream.pipeline.processors[0].runner  # through the fault wrapper
    misses0 = server.core.m_deadline_miss.value
    asyncio.run(asyncio.wait_for(stream.run(asyncio.Event()), timeout=120))
    assert stream.m_rows_out.value == 3  # nothing lost
    assert stream.m_errors.value >= 1  # the miss took the nack path
    assert server.core.m_deadline_miss.value >= misses0 + 1
    assert server.core.health.state == "healthy"  # probe re-admitted it


def test_generation_server_observability_metrics():
    """The observability satellites: slot/occupancy/tps gauges move, the
    eviction counter counts, and health_report carries the serving detail."""
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(10), cfg)

    async def go():
        server = GenerationServer(params, cfg, slots=2, page_size=4,
                                  max_seq=32, prefix_cache_pages=2)
        evict0 = server.m_prefix_evictions.value
        for base in (0, 30, 60):  # rotate the 2-page LRU -> evictions
            await server.generate(list(range(base + 1, base + 10)),
                                  max_new_tokens=3)
        await server.close()
        return server, evict0

    server, evict0 = asyncio.run(go())
    assert server.m_prefix_evictions.value > evict0
    assert server.m_tps.value > 0  # windowed tokens/sec was published
    # drained, but the prefix cache legitimately holds pages — occupancy
    # counts exactly those (cache-held / pool size, scratch excluded)
    total = server.num_pages - 1
    expected_occ = server._cache_held / total
    assert float(server.m_pool_occupancy.value) == pytest.approx(expected_occ)
    assert float(server.m_slots_busy.value) == 0.0
    rep = server.health_report()
    assert rep["serving"] == "continuous"
    assert rep["slots"] == 2 and rep["slots_busy"] == 0
    assert rep["page_pool_occupancy"] == pytest.approx(expected_occ, abs=1e-4)
    assert rep["prefix_cache"]["capacity_pages"] == 2
    assert "deadline_misses" in rep and rep["state"] == "healthy"


# -- page runs (PR 54): the kept pool's free pages come in blocks of neighbours --


def _take(free, n, pages=None):
    """``n`` more pages for a slot that holds ``pages``, as ``_alloc_page`` asks."""
    pages = [] if pages is None else pages
    for _ in range(n):
        pages.append(free.take(len(pages), pages[-1] if pages else None))
    return pages


def _blocks_are_aligned_and_ascending():
    from arkflow_tpu.tpu.serving import _FreePages

    free = _FreePages(1 + 40, run=4)
    a = _take(free, 10)
    assert a == list(range(1, 11))          # blocks 1-4, 5-8, and 9, 10 of the third
    b = _take(free, 5)
    assert b == [13, 14, 15, 16, 17]        # the lowest block that is whole: not 11, 12
    assert _take(free, 2, a) == list(range(1, 13))  # a goes on in its own block
    assert len(free) == 40 - 12 - 5


def _release_re_forms_blocks():
    from arkflow_tpu.tpu.serving import _FreePages

    free = _FreePages(1 + 24, run=4)
    a, b = _take(free, 7), _take(free, 6)
    assert (a, b) == ([1, 2, 3, 4, 5, 6, 7], [9, 10, 11, 12, 13, 14])
    for p in (5, 2, 7, 1, 4, 6, 3):         # a page at a time, in any order
        free.give(p)
    assert len(free) == 24 - 6
    assert _take(free, 8) == [1, 2, 3, 4, 5, 6, 7, 8]   # whole again, lowest first


def _single_pages_once_no_whole_block_is_free():
    from arkflow_tpu.tpu.serving import _FreePages

    free = _FreePages(1 + 12, run=4)
    held = [_take(free, 2) for _ in range(3)]
    assert held == [[1, 2], [5, 6], [9, 10]]
    # no block is whole: the blocks' free pages, lowest first, a neighbour
    # where the column is its place in its block (column 3: page 8)
    assert _take(free, 5) == [3, 4, 7, 8, 11]
    assert len(free) == 1 and free.take(5, 11) == 12 and len(free) == 0


def _a_short_last_block_is_single_pages():
    from arkflow_tpu.tpu.serving import _FreePages

    free = _FreePages(1 + 11, run=8)        # 8 neighbours and 3 pages past them
    assert _take(free, 11) == list(range(1, 12)) and len(free) == 0
    for p in range(1, 12):
        free.give(p)
    # columns 2..4 behind two pages of another pool's numbering: no run to go
    # on with, and a page of a block that is not whole before one that is
    assert _take(free, 3, [40, 41]) == [40, 41, 9, 10, 11]


ALLOCATOR = {f.__name__[1:]: f for f in (
    _blocks_are_aligned_and_ascending, _release_re_forms_blocks,
    _single_pages_once_no_whole_block_is_free, _a_short_last_block_is_single_pages)}


@pytest.mark.parametrize("case", sorted(ALLOCATOR))
def test_free_pages_come_in_runs(case):
    ALLOCATOR[case]()


@pytest.mark.parametrize("seed,run", [(0, 8), (1, 4), (2, 3)])
def test_free_pages_admit_what_a_plain_list_admits(seed, run):
    """The admission rule reads ``len(free)`` and takes that many pages: over
    any sequence of takes and gives the count is a plain list's, a take
    succeeds while it is not 0 and hands out a page that was free, once."""
    from arkflow_tpu.tpu.serving import _FreePages

    rng = np.random.RandomState(seed)
    n = 1 + 50
    free, plain = _FreePages(n, run=run), set(range(1, n))
    slots, cached = [[] for _ in range(4)], []
    for _ in range(600):
        s, move = slots[rng.randint(4)], rng.rand()
        if plain and move < 0.6:
            p = free.take(len(s), s[-1] if s else None)
            assert p in plain
            plain.remove(p)
            s.append(p)
        elif move < 0.8:                    # a slot finishes: some pages stay cached
            keep = [p for p in s if rng.rand() < 0.3]
            back = [p for p in s if p not in keep]
            cached += keep
            del s[:]
        else:                               # an eviction frees a page alone
            back = [cached.pop(rng.randint(len(cached)))] if cached else []
        for p in back if move >= 0.6 or not plain else []:
            free.give(p)
            plain.add(p)
        assert len(free) == len(plain)


def test_a_pool_of_one_slots_pages_serves_a_max_seq_request():
    """``num_pages`` exactly 1 + ``pages_per_slot``, that no multiple of
    ``PAGE_RUN`` (11 = 8 + 3): a request that grows to ``max_seq`` is served
    from a block and three single pages, token for token the reference's."""
    from arkflow_tpu.ops.ragged_attention import PAGE_RUN

    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(4), cfg)
    prompt = [3, 17, 42, 7, 91, 12, 8, 2]
    ref = _reference_generate(fam, params, cfg, prompt, max_new=36, eos_id=-1)

    async def go():
        server = GenerationServer(params, cfg, slots=1, page_size=4, max_seq=44,
                                  num_pages=12, eos_id=-1)
        assert server.pages_per_slot == 11 and server.pages_per_slot % PAGE_RUN
        task = asyncio.ensure_future(server.generate(prompt, max_new_tokens=36))
        while not task.done() and len(server._free_pages):
            await asyncio.sleep(0)
        pages = list(server._slot_pages[0])
        out = await task
        await server.close()
        return out, pages, len(server._free_pages)

    out, pages, free = asyncio.run(go())
    assert out == ref and len(out) == 36
    assert pages == list(range(1, 12)) and free == 11


def test_a_page_shared_through_the_prefix_cache_is_freed_alone():
    """Sharing and eviction stay a page's: the cache holds a finished
    prompt's full pages — the head of a block whose other pages went back
    —, a second request aliases them and takes its fresh pages elsewhere,
    and an eviction frees exactly the cached pages, after which the block
    is whole again and is handed out as one."""
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(9), cfg)
    common = list(range(3, 3 + 12))         # 3 full pages of 4

    async def go():
        server = GenerationServer(params, cfg, slots=2, page_size=4, max_seq=48,
                                  prefix_cache_pages=8)
        total = server.num_pages - 1
        await server.generate(common + [60, 61], max_new_tokens=5)
        (held,) = server._prefix_cache.values()
        assert held == [1, 2, 3] and len(server._free_pages) == total - 3
        # the second request: the three shared pages, then (columns 3..: no
        # run to go on with in a block it shares) the block's free pages
        before = dict(server._page_refs)
        await server.generate(common + [70, 71, 72], max_new_tokens=5)
        assert before == {1: 1, 2: 1, 3: 1} == dict(server._page_refs)
        assert server._evict_one() and not server._page_refs
        assert len(server._free_pages) == total
        pages = [server._alloc_page(p) for p in ([], [1], [1, 2])]
        await server.close()
        return pages

    assert asyncio.run(go()) == [1, 2, 3]


def test_pages_in_runs_counts_what_the_predicate_says():
    """``arkflow_gen_attn_pages_in_runs_total`` on a hand-made table of 16
    columns (``PAGE_RUN`` 8): a row walks 13 pages, both of its stretches
    neighbours — the first whole stretch counts, the second ends past its
    last page; a row walks all 16, its first stretch two pages swapped, its
    second neighbours; an idle lane walks its scratch page. A per-head
    server of heads narrower than 128 lanes (the narrow-head walk takes no
    runs) counts none; one of wide heads: ``tests/test_paged_kernel.py``."""
    from arkflow_tpu.obs import global_registry
    from arkflow_tpu.ops.ragged_attention import PAGE_RUN, pages_in_runs

    table = np.zeros((3, 16), np.int32)
    table[0] = [*range(1, 9), *range(20, 28)]
    table[1] = [9, 10, 12, 11, 13, 14, 15, 16, *range(30, 38)]
    last = np.asarray([100, 127, 0])         # pages of 8 keys: 13, 16 and 1
    assert PAGE_RUN == 8 and pages_in_runs(table, last // 8 + 1) == 8 + 8
    assert pages_in_runs(table[:, :12], np.asarray([12, 12, 1])) == 8  # a short tail

    fam = get_model("decoder_lm")
    latent = fam.make_config(
        vocab_size=128, dim=32, layers=3, heads=4, ffn=64, max_seq=128,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        rope_interleave=True, n_routed_experts=8, num_experts_per_tok=2,
        n_shared_experts=2, moe_intermediate_size=16, first_k_dense_replace=1)
    got = {}
    for name, cfg in (("latent", latent), ("per_head", fam.make_config(**TINY))):
        params = fam.init(jax.random.PRNGKey(1), cfg)
        server = GenerationServer(params, cfg, slots=3, page_size=8, max_seq=128,
                                  decode_kernel="paged", kernel_interpret=True)
        counters = [global_registry().counter(
            f"arkflow_gen_attn_{what}_total",
            labels={"model": "decoder_lm", "kind": "decode"})
            for what in ("pages_walked", "pages_in_runs")]
        before = [m.value for m in counters]
        server._note_walk("decode", last, 2, table=table)
        got[name] = [m.value - v for m, v in zip(counters, before)]
        asyncio.run(server.close())
    assert got == {"latent": [13 + 16 + 1, 16], "per_head": [30, 0]}
