"""A prefill chunk rides the decode step (tiny shapes, CPU).

Where a decode step is due and a slot is prefilling, a greedy server that
prefills in chunks issues ONE program for both
(``paged_decode.paged_fused_step``, ``GenerationServer._step(active,
riding)``) on a model that ``paged_decode.fusable`` admits — per-head K/V
with a dense MLP or routed experts, conv layers among its attention layers
or none, or plain latent attention with routed experts —: the lanes
and the chunk run as one row block through every weight product, attention
is the two steps' own two calls, a conv layer's windows are each part's own
rows of the conv pool, and a routed model's counters come back by
row range. What is served must be what a chunk and a decode step in turn
serve, request by request; every other model, a sampling and a speculative
server keep alternating and build no such program; the step takes one host
array; the counter ``arkflow_gen_chunks_total{mode}`` says what rode.
"""

from __future__ import annotations

import asyncio
import importlib.util
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arkflow_tpu.errors import ConfigError
from arkflow_tpu.models import decoder as dec
from arkflow_tpu.models import get_model
from arkflow_tpu.models.decoder import FULL, SLIDING
from arkflow_tpu.models.paged_decode import (fusable, init_page_pool,
                                             paged_decode_step,
                                             paged_fused_step, paged_prefill,
                                             paged_prefill_chunk,
                                             window_ring_pages)
from arkflow_tpu.obs import global_registry
from arkflow_tpu.tpu.serving import GenerationServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DENSE = dict(vocab_size=128, dim=64, layers=2, heads=4, kv_heads=2, ffn=96,
             max_seq=64)
#: a head of 128 lanes: the kernel that walks the page table itself
WIDE = dict(DENSE, dim=256, heads=2, kv_heads=1)
LATENT = dict(vocab_size=128, dim=32, layers=3, heads=4, ffn=64, max_seq=128,
              rope_theta=1e4, norm_eps=1e-6, kv_lora_rank=16,
              qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
              rope_interleave=True)
EXPERTS = dict(n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
               moe_intermediate_size=16, first_k_dense_replace=1)
HYBRID = dict(vocab_size=128, dim=64, layers=2, heads=4, kv_heads=2, head_dim=8,
              ffn=96, mamba_d_ssm=32, mamba_n_heads=2, mamba_d_head=16,
              mamba_d_state=16, mamba_n_groups=2, mamba_chunk_size=8)
#: the latent loop is served with routed experts only (the Kanana-2 layout)
ROUTED_LATENT = {**LATENT, **EXPERTS}
#: its sliding and indexed layers (the dots3 layout) and its several residual
#: streams (the Xing4.0 layout) each want an operand the block does not carry
PATTERN = dict(
    layer_types=(FULL, SLIDING, FULL), sliding_window=9, q_lora_rank=12,
    swa_heads=2, swa_q_lora_rank=12, swa_kv_lora_rank=24,
    swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4, swa_v_head_dim=8,
    swa_rope_theta=5e3, index_n_heads=4, index_head_dim=8, index_topk=16)
STREAMS = dict(hc_mult=2, hc_sinkhorn_iters=20, hc_eps=1e-6,
               mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)
#: routed experts on the per-head loop, and conv layers among the attention
#: layers with a dense MLP: each half of the LFM2 layout alone
ROUTED = {**DENSE, **EXPERTS}
CONV = dict(DENSE, layers=3, layer_types=("conv", FULL, "conv"), conv_L_cache=3)
#: the LFM2 layout: conv, conv, full, conv; two dense layers, then routed
#: under a selection bias, no shared expert, per-head norms. (Experts of 24:
#: at 16 this seed's bfloat16 logits TIE exactly — 1.140625 twice — at two
#: of the served tokens, and the two compiled programs, equal to the last
#: bit run op by op, break an exact tie differently)
CONV_ROUTED = dict(DENSE, layers=4, layer_types=("conv", "conv", FULL, "conv"),
                   conv_L_cache=3, qk_norm=True, n_routed_experts=8,
                   num_experts_per_tok=2, n_shared_experts=0,
                   moe_intermediate_size=24, first_k_dense_replace=2,
                   norm_topk_eps=1e-6, router_bias_std=0.1)
#: name -> (model, server options): what does NOT let a chunk ride
ALTERNATES = {
    "latent-pattern": ({**ROUTED_LATENT, **PATTERN}, {}),
    "latent-streams": ({**ROUTED_LATENT, **STREAMS}, {}),
    "routed-pattern": (dict(ROUTED, layer_types=(SLIDING, FULL), sliding_window=9),
                       {}),
    "switch": (dict(DENSE, num_experts=4), {}),
    "hybrid": (HYBRID, {}),
    "linear": (dict(DENSE, layers=3, layer_types=("linear_attention", FULL,
                                                  "linear_attention"),
                    linear_num_key_heads=2, linear_num_value_heads=4,
                    linear_key_head_dim=8, linear_value_head_dim=16,
                    linear_conv_kernel_dim=4), {}),
    "layered": (dict(DENSE, layer_types=(SLIDING, FULL), sliding_window=9), {}),
    "speculative": (DENSE, dict(speculative_tokens=2)),
    "sampling": (DENSE, dict(temperature=1.2, top_k=8, seed=42)),
    "one-shot": (DENSE, dict(prefill_chunk=0)),
}

#: more prompts than slots; at a chunk of 4 prompts of 22, 29 and 17 tokens
#: have first, middle and last chunks (the last short of a chunk), others are
#: one chunk or a one-shot prefill; budgets end at different steps
PROMPTS = [list(range(3, 25)), [9, 4], list(range(40, 55)), [7],
           list(range(60, 70)), [5, 6, 7], list(range(70, 99)),
           list(range(10, 27))]
BUDGETS = [6, 3, 5, 1, 4, 6, 9, 12]

_BUILT: dict = {}


def _model(model_kw: dict, tp: int = 0):
    key = (tuple(sorted((k, str(v)) for k, v in model_kw.items())), tp)
    if key not in _BUILT:
        fam = get_model("decoder_lm")
        cfg = fam.make_config(**model_kw)
        params, mesh = fam.init(jax.random.PRNGKey(11), cfg), None
        if tp:
            from arkflow_tpu.parallel.mesh import (MeshSpec, create_mesh,
                                                   shard_params)

            mesh = create_mesh(MeshSpec(tp=tp), devices=jax.devices()[:tp])
            axes = {n: n for n in mesh.axis_names}
            params = shard_params(params, fam.param_specs(cfg, axes), mesh)
        _BUILT[key] = (cfg, params, mesh)
    return _BUILT[key]


def _server(model_kw=DENSE, name="decoder_lm", tp=0, slots=3, **kw):
    if tp and len(jax.devices()) < tp:
        pytest.skip(f"needs {tp} virtual devices")
    cfg, params, mesh = _model(model_kw, tp)
    kw.setdefault("prefill_chunk", 4)
    return GenerationServer(params, cfg, slots=slots, page_size=4, max_seq=48,
                            eos_id=-1, mesh=mesh, name=name, **kw)


def _serve(server, prompts=PROMPTS, budgets=BUDGETS):
    """Every prompt at once. Returns the outputs and the steps the server
    made, in order: (kind, a step was in flight when it was issued)."""
    steps = []
    run_ahead, run_lockstep = server._run_ahead, server._run_device_step

    def ahead(key, *a, **kw):
        steps.append((key[0], server._pipeline is not None))
        return run_ahead(key, *a, **kw)

    def lockstep(key, *a, **kw):
        assert server._pipeline is None  # lockstep runs on a drained queue
        steps.append((key[0], False))
        return run_lockstep(key, *a, **kw)

    server._run_ahead, server._run_device_step = ahead, lockstep

    async def go():
        free0 = len(server._free_pages)
        outs = await asyncio.gather(*[
            server.generate(p, n) for p, n in zip(prompts, budgets)])
        await server.close()
        assert server._pipeline is None and server._gen_inflight == 0
        assert len(server._free_pages) == free0 and not server._prefill_pos
        return outs

    return asyncio.run(asyncio.wait_for(go(), timeout=240)), steps


def _counter(metric: str, name: str, **labels) -> float:
    return global_registry().counter(
        metric, labels={"model": name, **labels}).value


def _chunks(name: str) -> dict:
    return {mode: _counter("arkflow_gen_chunks_total", name, mode=mode)
            for mode in ("fused", "alone")}


# -- (b) the program: one pass is the two steps -----------------------------------------


@pytest.mark.parametrize("clen,start", [(8, 8), (5, 8), (8, 0)],
                         ids=["whole-chunk", "last-chunk", "first-chunk"])
@pytest.mark.parametrize("model_kw,kern", [
    (DENSE, "gather"), (DENSE, "paged"), (WIDE, "paged"),
    (ROUTED_LATENT, "gather"), (ROUTED_LATENT, "paged"),
    (ROUTED, "gather"), (CONV, "gather"),
    (CONV_ROUTED, "gather"), (CONV_ROUTED, "paged")],
    ids=["gather", "narrow-head-kernel", "wide-head-kernel", "latent-gather",
         "latent-kernel", "routed-gather", "conv-gather", "conv-routed-gather",
         "conv-routed-kernel"])
def test_fused_step_is_a_decode_step_then_a_chunk(model_kw, kern, clen, start):
    """``paged_fused_step``'s logits and pools equal ``paged_decode_step``
    then ``paged_prefill_chunk`` on the same inputs — both attention forms,
    both walks of the kernel, the latent loop through its own, a whole chunk,
    a prompt's short last one and its first, idle lanes (the prefilling
    slot's own, and one more) among the lanes. A routed model's counters by
    row range are what the two steps report apart, and the block's what both
    hit: the loads summed. A model with conv layers: the conv pool's rows too
    — the lanes' moved by their token, the chunk's row left as a chunk leaves
    it (a first chunk reads ZEROS, not its slot's earlier tenant's rows: the
    pool is seeded with noise), an idle lane's row untouched."""
    cfg, params, _ = _model(model_kw)
    lanes, cols, page, c = 4, 6, 4, 8
    rng = np.random.RandomState(clen + start)
    kp, vp = jax.tree_util.tree_map(
        lambda a: jnp.asarray(rng.randn(*a.shape), a.dtype),
        init_page_pool(cfg, 1 + lanes * cols, page, slots=lanes))
    table = jnp.asarray(1 + np.arange(lanes * cols).reshape(lanes, cols), jnp.int32)
    tok = jnp.asarray([5, 9, 0, 0], jnp.int32)
    lens = jnp.asarray([7, 13, 0, 0], jnp.int32)
    act = jnp.asarray([True, True, False, False])
    ids = jnp.asarray(rng.randint(0, 128, (1, c)), jnp.int32)
    off, n = jnp.asarray([start], jnp.int32), jnp.asarray([clen], jnp.int32)
    kw = dict(attention_kernel=kern, kernel_interpret=True)
    # the prompt sits in slot 2 (an idle lane): its state is the pool's row 3
    held = {"ssm_rows": jnp.asarray([3], jnp.int32)} if cfg.stateful else {}
    step, k1, v1, *lanes_moe = paged_decode_step(
        params, cfg, tok, lens, act, table, kp, vp, return_logits=True, **kw)
    chunk, k2, v2, *chunk_moe = paged_prefill_chunk(
        params, cfg, ids, off, n, table[2:3], k1, v1, **kw, **held)
    got, kf, vf, *moe = paged_fused_step(
        params, cfg, tok, lens, act, table, ids, off, n, table[2:3], kp, vp,
        return_logits=True, **kw, **held)
    assert got.shape == (lanes + 1, cfg.vocab_size)
    want = np.concatenate([np.asarray(step), np.asarray(chunk)])
    assert len(moe) == bool(cfg.routed)
    if moe:
        by_range = np.asarray(moe[0])
        np.testing.assert_array_equal(by_range[0], np.asarray(lanes_moe[0]))
        np.testing.assert_array_equal(by_range[1], np.asarray(chunk_moe[0]))
        # two active lanes and ``clen`` positions, two experts each in every
        # expert layer; the block hit no fewer experts than either part and
        # no more than both, its busiest expert no less than either's
        pairs, hit, load = by_range.T
        per = 2 * cfg.expert_layers
        assert list(pairs) == [2 * per, per * clen, per * (2 + clen)]
        assert max(hit[:2]) <= hit[2] <= min(hit[0] + hit[1], 8 * cfg.expert_layers)
        assert max(load[:2]) <= load[2] <= load[0] + load[1]
    # but the prompt's own lane: idle, it looks at its slot's first row, which
    # the chunk has written by then in the one and not in the other; nobody
    # reads an idle lane's token
    read = [0, 1, 3, 4]
    np.testing.assert_allclose(np.asarray(got)[read], want[read], atol=2e-5,
                               rtol=1e-5)
    assert (np.asarray(got).argmax(-1) == want.argmax(-1))[read].all()
    # every page but the scratch page, every row of a state pool but the
    # scratch row, which padding and idle lanes share
    for a, b in zip(jax.tree_util.tree_leaves((k2, v2)),
                    jax.tree_util.tree_leaves((kf, vf))):
        np.testing.assert_allclose(np.asarray(b[:, 1:], np.float32),
                                   np.asarray(a[:, 1:], np.float32),
                                   atol=2e-5, rtol=1e-5)
    if cfg.conv:
        before, after = (np.asarray(p["conv"], np.float32) for p in (kp, kf))
        assert (after[:, [1, 2]] != before[:, [1, 2]]).any()      # the lanes'
        np.testing.assert_array_equal(after[:, 4], before[:, 4])  # an idle lane's
        if not start:  # a short first chunk would keep the tenant's last row
            fresh = jax.tree_util.tree_map(jnp.zeros_like, (kp, vp))
            clean = paged_fused_step(
                params, cfg, tok, lens, act, table, ids, off, n, table[2:3],
                *fresh, return_logits=True, **kw, **held)[0]
            np.testing.assert_array_equal(np.asarray(clean)[lanes:],
                                          np.asarray(got)[lanes:])
    picked = paged_fused_step(params, cfg, tok, lens, act, table, ids, off, n,
                              table[2:3], kp, vp, **kw, **held)[0]
    assert picked.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(picked)[read], want.argmax(-1)[read])


@pytest.mark.parametrize("case", [k for k, v in ALTERNATES.items() if v[0] is not DENSE])
def test_the_program_refuses_what_needs_more_operands(case):
    """``fusable`` is what the code can see in the configuration; the fused
    program refuses the others by name."""
    cfg = get_model("decoder_lm").make_config(**ALTERNATES[case][0])
    assert not fusable(cfg) and fusable(_model(DENSE)[0])
    assert all(fusable(get_model("decoder_lm").make_config(**kw))
               for kw in (ROUTED_LATENT, ROUTED, CONV, CONV_ROUTED))
    with pytest.raises(ConfigError, match="rides a decode step only"):
        paged_fused_step(None, cfg, *[None] * 10)


# -- (a) what is served is what alternation serves ----------------------------------------


@pytest.mark.parametrize("depth", [1, 2], ids=["lockstep", "ahead"])
@pytest.mark.parametrize("case", ["gather", "paged", "tp2", "latent",
                                  "latent-paged", "routed", "conv",
                                  "conv-routed", "conv-routed-paged"])
def test_a_fusing_server_serves_what_an_alternating_one_serves(case, depth):
    """Greedy tokens of every request equal those of the same server made
    to alternate, over prompts whose first, middle and last chunks ride —
    in lockstep and one step ahead, through the gather form and the
    interpreted kernel, over a 2-device ``tp`` mesh, on the latent model
    with routed experts (whose steps all return ``_FusedLayout``'s array,
    the alternating ones too), and on the per-head loop's routed experts and
    conv layers, apart and together (the LFM2 layout: eight prompts over
    three slots, so a slot's conv row passes from tenant to tenant)."""
    kw = dict(dispatch_depth=depth, tp=2 if case == "tp2" else 0)
    models = {"latent": ROUTED_LATENT, "routed": ROUTED, "conv": CONV,
              "conv-routed": CONV_ROUTED}
    if case.removesuffix("-paged") in models:
        kw.update(model_kw=models[case.removesuffix("-paged")])
    if case.endswith("paged"):
        kw.update(decode_kernel="paged", kernel_interpret=True)
    alternating = _server(**kw)
    assert alternating._fuses and alternating._fused is not None
    alternating._fuses = False
    want, ref_steps = _serve(alternating)
    assert not any(kind == "fused" for kind, _ in ref_steps)
    assert [len(o) for o in want] == BUDGETS

    server = _server(**kw)
    rode, step = set(), server._step

    def spy(active, riding=-1):
        if riding >= 0:
            off, _, _, final = server._next_span(riding)
            rode.add("first" if off == 0 else "last" if final else "middle")
        return step(active, riding)

    server._step = spy
    got, steps = _serve(server)
    assert got == want
    assert rode == {"first", "middle", "last"}
    kinds = [kind for kind, _ in steps]
    assert kinds.count("fused") > 8
    # fewer device steps: a chunk that rides is no step of its own
    assert len(steps) <= len(ref_steps) - kinds.count("fused") + 2
    assert (depth == 2) == any(ahead for kind, ahead in steps if kind == "fused")
    assert server._fused.jitted._cache_size() == 1
    assert server._decode.jitted._cache_size() == 1
    if server._stateful:  # every tenancy began with a first chunk's reset
        assert [t[2] for t in server._state_tenant] == [3, 3, 2]


def test_every_seam_of_a_fused_step_is_crossed_ahead():
    """One step ahead: a fused step behind a fused step and behind a decode
    step, a decode step behind a fused step (the lanes take their tokens on
    the device from either kind's output), a step behind a fused step that
    carried its prompt's last chunk (that slot joins decode one step later,
    from the step's last token), and a lane masked out of the step behind
    the one that exhausts its budget."""
    server = _server()
    seen, real_fused, real_decode = [], server._fused, server._decode

    def spy(kind, real):
        def call(packed, *a):
            tok, _, act = (np.asarray(packed)[i * 3:(i + 1) * 3] for i in range(3))
            pend = server._pipeline
            seen.append((kind, None if pend is None else (pend.kind, pend.seeding),
                         bool((tok[act != 0] == -1).any()), bool(act.any())))
            return real(packed, *a)
        return call

    server._fused, server._decode = spy("fused", real_fused), spy("decode", real_decode)
    outs, _ = _serve(server)
    assert [len(o) for o in outs] == BUDGETS
    behind = {(kind, pend[0]) for kind, pend, _, _ in seen if pend}
    assert {("fused", "fused"), ("fused", "decode"), ("decode", "fused")} <= behind
    # a step issued while a fused step that seeds a slot is in flight
    assert any(pend and pend[0] == "fused" and pend[1] >= 0 for _, pend, _, _ in seen)
    # lanes ride a fused step, and a fused step's lanes ride on
    assert any(rides for kind, pend, rides, _ in seen if kind == "fused")
    assert any(rides for kind, pend, rides, _ in seen
               if kind == "decode" and pend and pend[0] == "fused")
    assert all(any_lane for *_, any_lane in seen)


def test_a_prompt_that_stops_after_prefill_keeps_its_last_chunks_own_step():
    """``prefill_export`` beside a decoding request: the prompt's chunks
    before its last ride the decode steps, its last runs alone against a
    drained queue (its pages are fetched right behind it), and the export
    equals an alternating server's."""
    def export(fuses):
        server = _server()
        server._fuses = fuses
        at_export, real = [], server._export_and_finish

        async def spy(slot):
            at_export.append(server._pipeline)
            await real(slot)

        server._export_and_finish = spy
        steps, step, alone = [], server._step, server._prefill_step

        def riding_spy(active, riding=-1):
            steps.append("fused" if riding >= 0 else "decode")
            return step(active, riding)

        def alone_spy(slot, kind="chunk"):
            steps.append(kind)
            return alone(slot, kind)

        server._step, server._prefill_step = riding_spy, alone_spy

        async def go():
            long = asyncio.ensure_future(server.generate(list(range(3, 25)), 24))
            while not server._tokens_emitted:
                await asyncio.sleep(0.001)
            out = await server.prefill_export(list(range(30, 52)), 4)
            return await long, out

        tokens, out = asyncio.run(asyncio.wait_for(go(), timeout=120))
        asyncio.run(server.close())
        assert at_export == [None]
        return tokens, out, steps

    tokens1, out1, steps1 = export(False)
    tokens2, out2, steps2 = export(True)
    assert "fused" not in steps1 and steps2.count("fused") == 5
    # 22 tokens at a chunk of 4: five chunks rode, the sixth ran alone
    assert steps2.count("chunk") == steps1.count("chunk") - 5
    assert tokens1 == tokens2 and out1["first_token"] == out2["first_token"]
    for a, b in zip(out1["k"] + out1["v"], out2["k"] + out2["v"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- (c) who does not fuse ---------------------------------------------------------------


@pytest.mark.parametrize("case", sorted(ALTERNATES))
def test_everything_else_still_alternates(case):
    """A latent model with a layer pattern or several residual streams, a
    per-head routed model with a layer pattern, a Switch, hybrid,
    linear-attention or layered model, a sampling and a
    speculative server, and one that prefills in one shot: no fused program
    is built, no step carries a chunk, and the chunks count as issued
    alone."""
    model_kw, server_kw = ALTERNATES[case]
    name = f"alternates-{case}"
    server_kw = {"prefill_chunk": 8, **server_kw}
    server = _server(model_kw, name=name, slots=2, **server_kw)
    assert not server._fuses and server._fused is None
    outs, steps = _serve(server, PROMPTS[:5], BUDGETS[:5])
    assert [len(o) for o in outs] == BUDGETS[:5]
    kinds = {kind for kind, _ in steps}
    assert "fused" not in kinds and kinds & {"decode", "verify"}
    chunks = _chunks(name)
    assert chunks["fused"] == 0
    assert chunks["alone"] == sum(kind == "chunk" for kind, _ in steps)
    assert (chunks["alone"] > 0) == bool(server.prefill_chunk)


# -- (d) one host array a fused step, and the counter counts what rode ---------------------


@pytest.mark.parametrize("depth", [1, 2], ids=["lockstep", "ahead"])
@pytest.mark.parametrize("family", ["dense", "latent", "conv-routed"])
def test_a_fused_step_takes_one_host_array_and_counts_its_chunk(family, depth):
    """``arkflow_gen_step_uploads_total{kind="fused"}`` moves by one a fused
    step (the decode step's operands and the chunk's go up as ONE array;
    the step before's tokens — and a routed prompt's counters so far — stay
    on the device), the array is the two steps' packed arrays end to end,
    ``arkflow_gen_chunks_total{mode}`` counts every chunk once, by the step
    that carried it, and the host fetches one array a step."""
    name = f"fused-uploads-{family}-{depth}"
    server = _server({"dense": DENSE, "latent": ROUTED_LATENT,
                      "conv-routed": CONV_ROUTED}[family], name=name,
                     dispatch_depth=depth)
    fused_steps, host_arrays, sizes = [0], [], set()
    real = server._fused

    def counted(*args):
        fused_steps[0] += 1
        host_arrays.append(sum(isinstance(a, np.ndarray) for a in args))
        # (a model with a state pool carries its pools as dicts by name)
        assert all(isinstance(a, (np.ndarray, jax.Array))
                   for a in jax.tree_util.tree_leaves(args))
        sizes.add(args[0].shape)
        out = real(*args)
        # tokens (and a routed model's counters) in ONE array, then the pools
        assert len(out) == 3 and out[0].ndim == 1
        return out

    server._fused = counted
    alone, real_chunk = [0], server._chunk

    def counted_chunk(*args):
        alone[0] += 1
        return real_chunk(*args)

    server._chunk = counted_chunk
    outs, steps = _serve(server)
    assert [len(o) for o in outs] == BUDGETS
    assert fused_steps[0] > 8 and set(host_arrays) == {1}
    assert _counter("arkflow_gen_step_uploads_total", name, kind="fused") \
        == fused_steps[0]
    # a table row of a model with a state a slot ends with the slot's row
    # of the state pool: the lanes' and the chunk's
    pages = server.pages_per_slot + (family == "conv-routed")
    assert sizes == {(3 * (3 + pages) + 4 + 2 + pages,)}
    assert _chunks(name) == {"fused": fused_steps[0], "alone": alone[0]}
    # every chunk of every prompt longer than a chunk, once (a model with
    # a state pool prefills in chunks only: the short prompts' too)
    assert fused_steps[0] + alone[0] == sum(
        -(-len(p) // 4) for p in PROMPTS if len(p) > 4 or server._stateful)
    # the loop's own stages are observed once a device step, fused or not
    if depth == 2:
        assert _counter("arkflow_gen_steps_ahead_total", name, kind="fused") > 0


# -- (e) a routed model's counters by the program that ran ----------------------------------


def _routing(name: str, kind: str) -> tuple:
    """(pairs, steps observed, distinct experts summed, largest loads summed)
    of the routing series ``kind`` of the server ``name``."""
    reg, labels = global_registry(), {"model": name, "kind": kind}
    hit = reg.histogram("arkflow_gen_moe_experts_hit", labels=labels)
    load = reg.histogram("arkflow_gen_moe_max_load", labels=labels)
    return (_counter("arkflow_gen_moe_assignments_total", name, kind=kind),
            hit.count, hit.sum, load.sum)


@pytest.mark.parametrize("depth", [1, 2], ids=["lockstep", "ahead"])
@pytest.mark.parametrize("family", ["latent", "conv-routed"])
def test_routing_counters_go_by_the_program_that_ran(family, depth):
    """On a seeded server of the latent model with routed experts, and of
    the LFM2 layout on the per-head loop (each two
    experts a token, two expert layers: four pairs a token): ``decode`` is
    the ``_decode`` executions' alone and takes nothing from a fused step;
    a fused step's lanes go under ``fused_lanes`` and its block — lanes and
    chunk, what the expert products read — under ``fused``; ``chunk`` is
    every prompt's chunks, fused or alone, summed on the device and recorded
    with its first token. Beside an alternating server's the totals agree."""
    names = {fuses: f"moe-kinds-{family}-{depth}-{fuses}" for fuses in (True, False)}
    seen = {}
    state = family == "conv-routed"  # a table row's last column
    for fuses, name in names.items():
        server = _server(CONV_ROUTED if state else ROUTED_LATENT, name=name,
                         dispatch_depth=depth)
        assert server._fuses and server._lay.size == 3 + 2 + 3 * 3
        server._fuses = fuses
        steps, apply, fused = [], server._apply_decode, server._fused

        def applied(act, nxt, reqs=None, seeds=None, rode=0, steps=steps,
                    apply=apply):
            steps.append((int(act.sum()), rode))
            return apply(act, nxt, reqs=reqs, seeds=seeds, rode=rode)

        def issued(packed, *a, steps=steps, fused=fused,
                   at=3 * (3 + server.pages_per_slot + state) + 4 + 1):
            steps.append(("rode", int(np.asarray(packed)[at])))  # its tokens
            return fused(packed, *a)

        server._apply_decode, server._fused = applied, issued
        outs, _ = _serve(server)
        assert [len(o) for o in outs] == BUDGETS
        seen[fuses] = steps
    # every token but a request's last goes through a lane once
    lane_tokens = sum(BUDGETS) - len(BUDGETS)
    for fuses, name in names.items():
        lanes = [s for s in seen[fuses] if s[0] != "rode"]
        rode = sum(n for kind, n in seen[fuses] if kind == "rode")
        alone = [n for n, r in lanes if not r]
        fused = [n for n, r in lanes if r]
        assert bool(fused) == fuses and sum(alone) + sum(fused) == lane_tokens
        pairs, steps, hit, load = _routing(name, "decode")
        assert (pairs, steps) == (4 * sum(alone), len(alone))
        assert 2 * len(alone) <= 2 * hit <= pairs and load <= sum(alone)
        pairs, steps, *_ = _routing(name, "fused_lanes")
        assert (pairs, steps) == (4 * sum(fused), len(fused))
        pairs, steps, hit, load = _routing(name, "fused")
        assert (pairs, steps) == (4 * (sum(fused) + rode), len(fused))
        assert fuses == (rode > 0)
        # a prompt's chunks: every token of every prompt longer than a chunk
        # (a model with a state pool prefills in chunks only)
        chunked = [p for p in PROMPTS if len(p) > 4 or state]
        pairs, steps, *_ = _routing(name, "chunk")
        assert pairs == 4 * sum(map(len, chunked))
        assert steps == sum(-(-len(p) // 4) for p in chunked)
        pairs, steps, *_ = _routing(name, "prefill")
        assert (pairs, steps) == (4 * sum(len(p) for p in PROMPTS
                                          if p not in chunked),
                                  len(PROMPTS) - len(chunked))
    # fused or not, the same lanes and the same prompts were routed
    assert (_routing(names[True], "decode")[0] + _routing(names[True], "fused_lanes")[0]
            == _routing(names[False], "decode")[0])
    assert _routing(names[True], "chunk") == _routing(names[False], "chunk")


def test_a_fused_step_counts_its_expert_product_by_its_block(monkeypatch):
    """``arkflow_gen_moe_grouped_products_total``: a fused step's expert
    products took the block's rows (lanes + chunk) and are counted once, under
    ``fused``, by that row count; a decode step's by the lanes alone."""
    from arkflow_tpu.ops import moe_experts

    name = "moe-grouped-fused"
    server = _server(ROUTED_LATENT, name=name)
    asyncio.run(server.close())
    monkeypatch.setattr(server, "decode_kernel", "paged")
    monkeypatch.setattr(moe_experts, "runs_grouped", lambda rows: rows > 5)
    lay = server._lay
    nxt = np.arange(lay.size)
    server._note_step_moe(nxt, 4)       # 3 lanes + 4 chunk rows: above "a tile"
    server._note_step_moe(nxt, 0)       # 3 lanes
    grouped = {kind: _counter("arkflow_gen_moe_grouped_products_total", name,
                              kind=kind) for kind in ("fused", "fused_lanes", "decode")}
    assert grouped == {"fused": server._moe_layers, "fused_lanes": 0, "decode": 0}
    # the lanes' three and the block's three are read from their own places
    assert _routing(name, "fused_lanes")[0] == nxt[lay.lanes_at]
    assert _routing(name, "fused")[0] == nxt[lay.lanes_at + lay.n]
    assert _routing(name, "decode")[0] == nxt[lay.lanes_at]


# -- (f) the programs that must not move -------------------------------------------------------

#: sha256 (first 16 hex) of the jaxprs (source positions stripped, as
#: ``tests/test_window_gqa_moe.py`` strips them) of the steps this file's
#: change must leave alone, RECORDED AT ITS PARENT (47611bf): the latent loop
#: without a riding chunk at a tiny Kanana-2 layout (its one-shot prefill
#: too), a tiny dots3 layout (sliding and indexed layers, a held share) and a
#: tiny Xing4.0 layout (four residual streams, YaRN), through the kernels and
#: through the gather form; and the dense per-head fused step (the Mistral
#: cells'). The first four ``paged`` hashes are ``BYPASS_GOLDEN``'s. PR 58
#: added what ITS change must leave alone, recorded at its parent.
#: PR 60 RE-RECORDED ``mistral.fused.paged``: it changed the
#: kernel's body on purpose (both products take the type the pools hold; a
#: chunk tile reads a head's rows out of the slot's own words), so every
#: program that holds ``_paged_kernel`` moved and nothing else did: the latent,
#: ``lfm2.*`` and every ``gather`` entry stand as recorded, which is the bypass
#: PR 60 owes
#: PR 61 RE-RECORDED ``mistral.fused.paged`` (85e2cb4948cd216b before it): its walk is ``_page_walk``'s (a run of ``PAGE_RUN`` neighbours a copy out of pools that
#: ride as flat rows, a program's last step starting the next program's first
#: group): every program that holds ``_paged_kernel`` moved — a window call's
#: too, whose walk takes no runs but shares the copies and the hand-on — and
#: nothing else did
PARENT_GOLDEN = {
    "kanana2.decode.paged": "fb8bb5e87fc3416b", "kanana2.decode.gather": "b709f765129bcb03",
    "kanana2.chunk.paged": "12309b7328d12d1a", "kanana2.chunk.gather": "328c7e20c9e1bcd0",
    "kanana2.prefill.paged": "434205b7e2a363a9", "kanana2.prefill.gather": "3a36506ef823537a",
    "dots3.decode.paged": "095ffd5cfa34a880", "dots3.decode.gather": "d33397f1f3923efa",
    "dots3.chunk.paged": "d8c6eb810c5ac125", "dots3.chunk.gather": "b001d947ccbce8e8",
    "xing4.decode.paged": "d336b92619bfd470", "xing4.decode.gather": "60a794ee6db7674d",
    "xing4.chunk.paged": "8e15ccabd252533b", "xing4.chunk.gather": "ade850137b63adb5",
    "mistral.fused.paged": "aa2676b9bea00ba3", "mistral.fused.gather": "67d47148371a1896",
    # recorded at PR 58's parent (15665ab): an LFM2 layout (conv, conv, full,
    # conv; two dense layers, then routed) without a riding chunk
    "lfm2.decode.paged": "b4f07a44820525ad", "lfm2.decode.gather": "b08a99f7fadeafec",
    "lfm2.chunk.paged": "8963e06a9eddc080", "lfm2.chunk.gather": "309068d48b2977f6",
    # and the latent fused step (``kanana2_l6``'s), at the same parent
    "kanana2.fused.paged": "06c6e0ab99066c9f", "kanana2.fused.gather": "931ec15d39328497"}

_KANANA = dict(vocab_size=64, dim=32, layers=3, heads=4, ffn=48, max_seq=64,
               kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
               v_head_dim=8, rope_interleave=True, n_routed_experts=8,
               num_experts_per_tok=2, n_shared_experts=1,
               moe_intermediate_size=16, first_k_dense_replace=1)
_LAYOUTS = {
    "kanana2": _KANANA,
    "dots3": dict(_KANANA, **dict(PATTERN, layer_types=(FULL, SLIDING, SLIDING, FULL)),
                  layers=4, experts_held=(4, 2)),
    "xing4": dict(_KANANA, **dict(STREAMS, hc_mult=4), dim=128, qk_rope_head_dim=8,
                  q_lora_rank=12, routed_scaling_factor=2.0,
                  rope_scaling={"type": "yarn", "factor": 64,
                                "original_max_position_embeddings": 16,
                                "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                                "mscale_all_dim": 1}),
    "mistral": dict(vocab_size=64, dim=256, layers=2, heads=2, kv_heads=1, ffn=48,
                    max_seq=64),
    "lfm2": dict(vocab_size=64, dim=32, layers=4, heads=4, kv_heads=2, head_dim=64,
                 ffn=48, max_seq=64, qk_norm=True, conv_L_cache=3,
                 layer_types=("conv", "conv", FULL, "conv"), n_routed_experts=8,
                 num_experts_per_tok=2, n_shared_experts=0,
                 moe_intermediate_size=16, first_k_dense_replace=2,
                 norm_topk_eps=1e-6, router_bias_std=0.1)}


def _window_goldens():
    """``tests/test_window_gqa_moe.py``: how a recorded jaxpr is stripped of
    its source positions and hashed (``BYPASS_GOLDEN``'s way)."""
    if "window" not in _BUILT:
        spec = importlib.util.spec_from_file_location(
            "window_goldens", os.path.join(ROOT, "tests", "test_window_gqa_moe.py"))
        _BUILT["window"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(_BUILT["window"])
    return _BUILT["window"]


@pytest.mark.parametrize("case", sorted(PARENT_GOLDEN))
def test_programs_without_a_riding_chunk_are_the_parents(case):
    layout, step, kern = case.split(".")
    cfg = dec.DecoderConfig(**_LAYOUTS[layout])
    p = jax.eval_shape(lambda: dec.init(jax.random.PRNGKey(0), cfg))
    kp, vp = jax.eval_shape(lambda: init_page_pool(
        cfg, 9, 8, 9 if cfg.layered else 0, slots=2))
    held = {"ssm_rows": jnp.ones((1,), jnp.int32)} if cfg.stateful else {}
    kw = dict(attention_kernel=kern, kernel_interpret=False)
    i32 = jnp.int32

    def tables(rows, step_tokens):
        kept = jnp.zeros((rows, 4), i32)
        if not cfg.layered:
            return kept
        return kept, jnp.zeros((rows, window_ring_pages(cfg, 8, step_tokens)), i32)

    def lanes():  # made under the trace, as the recorded ones were
        return jnp.zeros((2,), i32), jnp.ones((2,), i32), jnp.ones((2,), bool)

    def chunk():
        return jnp.zeros((1, 8), i32), jnp.zeros((1,), i32), jnp.full((1,), 5, i32)

    jaxpr = jax.make_jaxpr({
        "decode": lambda p, k, v: paged_decode_step(
            p, cfg, *lanes(), tables(2, 1), k, v, **kw),
        "chunk": lambda p, k, v: paged_prefill_chunk(
            p, cfg, *chunk(), tables(1, 8), k, v, **kw, **held),
        "prefill": lambda p, k, v: paged_prefill(
            p, cfg, jnp.zeros((1, 8), i32), jnp.full((1,), 5, i32), tables(1, 8),
            k, v, **kw),
        "fused": lambda p, k, v: paged_fused_step(
            p, cfg, *lanes(), tables(2, 1), *chunk(), tables(1, 8), k, v, **kw),
    }[step])(p, kp, vp)
    got = _window_goldens()._text_hash(_window_goldens()._jaxpr_text(jaxpr))
    assert got == PARENT_GOLDEN[case], got


# -- the benchmark's three readers -------------------------------------------------------------


def _reader(metric: str):
    spec = importlib.util.spec_from_file_location(
        metric, os.path.join(ROOT, "benchmark", "metrics", metric + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _View:
    """``run.py::View``'s calls over fixed window deltas."""

    def __init__(self, fused=0.0, alone=0.0, modules=None, busy=(), prompts=(),
                 chips=1):
        self._chunks = {"fused": fused, "alone": alone}
        self.trace = None if modules is None else {"modules": modules}
        # Mistral-7B's widths at six layers; a chunk and a budget of 128
        self.sizes = dict(hidden_size=4096, num_hidden_layers=6,
                          num_attention_heads=32, num_key_value_heads=8,
                          intermediate_size=14336, vocab_size=32768)
        self.proc_cfg = dict(prefill_chunk=128, max_new_tokens=128)
        self.peaks, self.chips = dict(hbm_bytes_per_s=819e9), chips
        self.run = types.SimpleNamespace(
            pool=types.SimpleNamespace(tokens=np.asarray(prompts, np.int64)))
        self._busy = list(busy)

    def gauge(self, name):
        assert name == "arkflow_gen_slots_busy"
        return self._busy

    def counter(self, name, **labels):
        assert name == "arkflow_gen_chunks_total"
        return (self._chunks[labels["mode"]] if labels
                else sum(self._chunks.values()))


CELLS = ["mistral_l6.summarize_backlog", "mistral_tp4.summarize_backlog"]


@pytest.mark.parametrize("fused,alone,want", [
    (90.0, 10.0, 90.0), (3400.0, 25.0, 3400 / 34.25), (0.0, 80.0, 0.0),
    (0.0, 0.0, None)],
    ids=["most-ride", "a-window", "all-alone", "parent-or-no-chunks"])
def test_gen_chunks_fused_pct_reader(fused, alone, want):
    """``benchmark/metrics/gen_chunks_fused_pct.py``: the ``mode="fused"``
    share of the chunks issued in the window, in percent; nothing on a
    program without the counter (the parent) or a window without chunks."""
    got = _reader("gen_chunks_fused_pct").read(_View(fused, alone))
    assert got == want if want is None else got == pytest.approx(want)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "gen_chunks_fused_pct"]
    assert entry == {
        "name": "gen_chunks_fused_pct", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler, generate",
        "moves": "tokens_per_s", "workloads": CELLS}


@pytest.mark.parametrize("modules,want", [
    ({"jit__fused(123)": [0.006, 0.0058, 0.0062], "jit__decode(7)": [0.0048]}, 6.0),
    ({"jit__decode(7)": [0.0048], "jit__chunk(9)": [0.0049]}, None),
    (None, None)], ids=["fused-steps", "the-parent", "no-trace"])
def test_fused_step_ms_reader(modules, want):
    """``benchmark/metrics/fused_step_ms.py``: median device time of the
    ``jit__fused`` program's executions; nothing where there is none (the
    parent, an untraced run) — and the accepted readers of ``jit__decode``
    / ``jit__chunk`` do not match its name."""
    got = _reader("fused_step_ms").read(_View(modules=modules))
    assert got == want if want is None else got == pytest.approx(want)
    if modules and want:
        view = _View(modules=modules)
        assert _reader("decode_step_ms").read(view) == pytest.approx(4.8)
        assert _reader("prefill_chunk_ms").read(view) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "fused_step_ms"]
    assert entry == {
        "name": "fused_step_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "device step, generate",
        "moves": "tokens_per_s", "workloads": CELLS}


@pytest.mark.parametrize("prompts,want", [
    ([300], (128 + 256 + 300) / 3), ([64, 128], 0.0), ([129, 640, 100],
     (128 + 129 + 128 * 10 + 640) / 7)],
    ids=["three-chunks", "one-shot-prompts", "a-mix"])
def test_a_chunk_attends_over_its_prompt_so_far(prompts, want):
    """``fused_hbm_pct``'s chunk term: chunk i of a prompt of n tokens reads
    min(i x C, n) tokens; a prompt of one chunk or less is a one-shot
    prefill and has no chunk."""
    got = _reader("fused_hbm_pct").chunk_kv_tokens(prompts, 128)
    assert got == pytest.approx(want)


@pytest.mark.parametrize("modules,busy,chips,want", [
    ({"jit__fused(1)": [0.0054, 0.0055, 0.0053], "jit__decode(7)": [0.0048]},
     [16.0, 16.0], 1, "read"),
    ({"jit__fused(1)": [0.0054], "jit__decode(7)": [0.0048]}, [16.0], 4, "read"),
    ({"jit__decode(7)": [0.0048], "jit__chunk(9)": [0.0049]}, [16.0], 1, None),
    ({"jit__fused(1)": [0.0054]}, [], 1, None),
    (None, [16.0], 1, None)],
    ids=["fused-steps", "four-chips", "the-parent", "no-gauge", "no-trace"])
def test_fused_hbm_pct_reader(modules, busy, chips, want):
    """``benchmark/metrics/fused_hbm_pct.py``: ``decode_hbm_pct``'s bytes —
    the weights ONCE, the K/V of one lane fewer (the slot whose prompt rides
    does not decode) — plus the K/V the chunk attends over, over the peak
    and the ``jit__fused`` program's median time, not clamped; nothing on
    the parent (no such module), without the gauge or without a trace."""
    prompts = [512] * 8
    view = _View(modules=modules, busy=busy, prompts=prompts, chips=chips)
    got = _reader("fused_hbm_pct").read(view)
    if want is None:
        assert got is None
    else:
        from benchmark.lib.costs import decode_step_bytes

        # 15 lanes at 512 + 64 tokens; chunks end at 128, 256, 384, 512
        nbytes = decode_step_bytes(
            dim=4096, layers=6, heads=32, kv_heads=8, ffn=14336, vocab=32768,
            kv_tokens=15 * 576 + 320, chips=chips)
        assert got == pytest.approx(100 * nbytes / 819e9 / 0.0054)
        # at one chip: the weights' 2.9 GB and 0.22 GB of K/V in 5.4 ms
        assert (60 < got < 80) if chips == 1 else (15 < got < 20)
        # the pure decode steps' share is read from the decode program alone
        pure = _reader("decode_hbm_pct").read(view)
        assert pure > got * 0.0054 / 0.0048
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "fused_hbm_pct"]
    assert entry == {
        "name": "fused_hbm_pct", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "tokens_per_s", "workloads": CELLS}
    # the newest entry when PR 48 added it; later PRs append behind it
    assert entry in bench["per_layer"]
