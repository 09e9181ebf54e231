"""One generate step ahead of the device (tiny shapes, CPU).

The serve loop enqueues step N+1 of any kind (chunk, decode, one-shot
prefill) before step N is waited for, fetched and applied
(``GenerationServer._run_ahead``). What is served must be what
``dispatch_depth: 1`` (lockstep) serves, request by request; where running
ahead is not exact the server falls back to lockstep by what it observes;
a failure with two steps in flight fails both steps' requests and leaves the
page ledger whole; every step is still observed once, under its kind. A
model that carries a recurrent state a slot (a Mamba-2 state, conv windows, a
delta rule's matrix state) runs ahead where no EOS is live, and leaves in
every slot's row of the state pool what lockstep leaves.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

import jax
import numpy as np
import pytest

from arkflow_tpu.errors import StepDeadlineExceeded
from arkflow_tpu.models import get_model
from arkflow_tpu.obs import global_registry
from arkflow_tpu.tpu.health import HealthConfig
from arkflow_tpu.tpu.serving import GenerationServer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DENSE = dict(vocab_size=128, dim=64, layers=2, heads=4, kv_heads=2, ffn=96,
             max_seq=64)
#: latent attention + dropless top-2 of 8 routed experts behind a dense layer
ROUTED = dict(vocab_size=128, dim=32, layers=3, heads=4, ffn=64, max_seq=128,
              rope_theta=1e4, norm_eps=1e-6, kv_lora_rank=16,
              qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
              rope_interleave=True, n_routed_experts=8, num_experts_per_tok=2,
              n_shared_experts=2, moe_intermediate_size=16,
              first_k_dense_replace=1, routed_scaling_factor=2.448)
#: a Mamba-2 mixer beside GQA attention in every layer: a recurrent state a slot
HYBRID = dict(vocab_size=128, dim=64, layers=2, heads=4, kv_heads=2, head_dim=8,
              ffn=96, rope_theta=100000000000, mamba_d_ssm=32, mamba_n_heads=2,
              mamba_d_head=16, mamba_d_state=16, mamba_n_groups=2,
              mamba_chunk_size=8, embedding_multiplier=5.6,
              attention_out_multiplier=0.5, key_multiplier=0.3,
              ssm_in_multiplier=0.25, ssm_out_multiplier=0.4,
              ssm_multipliers=[0.35, 0.25, 0.18, 0.5, 0.35],
              mlp_multipliers=[0.18, 0.3], lm_head_multiplier=0.0078125)
#: gated short convolutions among GQA layers of 64-wide heads, two dense
#: layers and then routed experts (the LFM2 layout, ``test_conv_gqa_moe.py``
#: at four layers): a window of two gated inputs a slot
CONV = dict(vocab_size=128, dim=32, layers=4, heads=4, kv_heads=2, head_dim=64,
            ffn=64, max_seq=256, rope_theta=1e6, norm_eps=1e-5, qk_norm=True,
            layer_types=("conv", "conv", "full_attention", "conv"),
            conv_L_cache=3, n_routed_experts=8, num_experts_per_tok=2,
            n_shared_experts=0, moe_intermediate_size=16,
            first_k_dense_replace=2, norm_topk_eps=1e-6, router_bias_std=0.1)
#: Gated DeltaNet layers among gated GQA layers, every layer routed (the
#: Qwen3-Next layout, ``test_gdn_gqa_moe.py`` at three layers and heads of
#: 128): a float32 matrix state and a three-row window a slot
GDN = dict(vocab_size=128, dim=32, layers=3, heads=4, kv_heads=2, head_dim=128,
           ffn=64, max_seq=256, rope_theta=1e7, norm_eps=1e-6, qk_norm=True,
           partial_rotary_factor=0.25, attention_gate_type="elementwise",
           norm_unit_offset=True,
           layer_types=("linear_attention", "full_attention", "linear_attention"),
           linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=16, linear_value_head_dim=128,
           linear_conv_kernel_dim=4, n_routed_experts=16, num_experts_per_tok=3,
           n_shared_experts=1, shared_expert_gate=True, moe_intermediate_size=16,
           first_k_dense_replace=0, scoring_func="softmax", topk_method="greedy")
#: the three kinds of state a slot
STATE_KINDS = {"hybrid": HYBRID, "conv": CONV, "gdn": GDN}
#: the capacity-based Switch block: a step's live lanes share expert capacity
SWITCH = dict(vocab_size=128, dim=32, layers=2, heads=2, kv_heads=1, ffn=48,
              max_seq=64, num_experts=4)

#: DENSE with top-2 of 8 routed experts behind a dense layer (its chunks ride
#: its decode steps since PR 58) ...
PER_HEAD_ROUTED = dict(DENSE, n_routed_experts=8, num_experts_per_tok=2,
                       n_shared_experts=1, moe_intermediate_size=16,
                       first_k_dense_replace=1)
#: ... and with a sliding layer before its full one: its chunks do not ride
#: (``paged_decode.fusable``: the block carries no ring coordinates), so its
#: server ALTERNATES them — as a dense server did before a chunk could ride
ALTERNATING = dict(PER_HEAD_ROUTED, sliding_window=9,
                   layer_types=("sliding_attention", "full_attention"))

#: more prompts than slots; chunks before a last one (22, 15 tokens at chunk
#: 4 or 8), prompts of one chunk, and budgets that end at different steps
PROMPTS = [list(range(3, 25)), [9, 4], list(range(40, 55)), [7],
           list(range(60, 70)), [5, 6, 7]]
BUDGETS = [6, 3, 5, 1, 4, 6]

_BUILT: dict = {}


def _model(model_kw: dict):
    key = tuple(sorted((k, str(v)) for k, v in model_kw.items()))
    if key not in _BUILT:
        fam = get_model("decoder_lm")
        cfg = fam.make_config(**model_kw)
        _BUILT[key] = (cfg, fam.init(jax.random.PRNGKey(11), cfg))
    return _BUILT[key]


def _server(model_kw, name="decoder_lm", **kw):
    cfg, params = _model(model_kw)
    kw.setdefault("eos_id", -1)
    kw.setdefault("prefill_chunk", 4)
    return GenerationServer(params, cfg, slots=2, page_size=4, max_seq=48,
                            name=name, **kw)


def _serve(server, prompts=PROMPTS, budgets=BUDGETS):
    """Every prompt at once through two slots. Returns the outputs and the
    steps the server made, in order: (kind, ran ahead of a step in flight,
    fetched)."""
    steps = []
    run_ahead, run_lockstep = server._run_ahead, server._run_device_step

    def ahead(key, packed, dev, apply=None, **kw):
        steps.append((key[0], server._pipeline is not None, apply is not None))
        return run_ahead(key, packed, dev, apply, **kw)

    def lockstep(key, *a, final=True, **kw):
        assert server._pipeline is None  # lockstep runs on a drained queue
        steps.append((key[0], False, final))
        return run_lockstep(key, *a, final=final, **kw)

    server._run_ahead, server._run_device_step = ahead, lockstep

    async def go():
        free0 = len(server._free_pages)
        outs = await asyncio.gather(*[
            server.generate(p, n) for p, n in zip(prompts, budgets)])
        await server.close()
        assert server._pipeline is None and server._gen_inflight == 0
        assert len(server._free_pages) == free0 - server._cache_held
        return outs

    return asyncio.run(asyncio.wait_for(go(), timeout=180)), steps


KINDS = ("decode", "chunk", "prefill", "fused")


def _ahead_counts(name: str) -> dict:
    reg = global_registry()
    return {kind: reg.counter("arkflow_gen_steps_ahead_total",
                              labels={"model": name, "kind": kind}).value
            for kind in KINDS}


# -- the same tokens as lockstep, and the counter says it engaged ------------------------


@pytest.mark.parametrize("model_kw,server_kw", [
    (DENSE, dict()),
    (DENSE, dict(prefill_chunk=8, prefix_cache_pages=8)),
    (DENSE, dict(prefill_chunk=0)),
    (DENSE, dict(decode_kernel="paged", kernel_interpret=True)),
    (ROUTED, dict(prefill_chunk=8)),
    (DENSE, dict(eos_id=57)),
    (DENSE, dict(eos_id=24, prefill_chunk=8)),
    (ROUTED, dict(prefill_chunk=8, eos_id=1)),
    (HYBRID, dict(prefill_chunk=8)),
    (CONV, dict(prefill_chunk=8)),
    (GDN, dict(prefill_chunk=8)),
], ids=["dense-chunked", "dense-prefix-cache", "dense-one-shot", "dense-paged",
        "routed", "dense-live-eos", "dense-live-eos-first", "routed-live-eos",
        "recurrent-state", "conv-windows", "delta-rule-state"])
def test_running_ahead_serves_what_lockstep_serves(model_kw, server_kw):
    """Token streams of every request equal ``dispatch_depth: 1``'s: dense
    greedy with chunked prefill and more prompts than slots, the prefix
    cache, one-shot prefill, the paged kernel, a dropless-routed model, a
    live EOS (a lane rides one step too long and its token is dropped), and
    a state a slot of each kind with no EOS live (every end is a budget's,
    known a step early). The counter ``arkflow_gen_steps_ahead_total`` moves
    with the steps that ran ahead."""
    name = "ahead-" + "-".join(f"{k}{v}" for k, v in sorted(server_kw.items()))
    name += "-" + str(len(model_kw))
    want, ref_steps = _serve(_server(model_kw, dispatch_depth=1, **server_kw))
    assert not any(ahead for _, ahead, _ in ref_steps)
    before = _ahead_counts(name)
    server = _server(model_kw, name=name, **server_kw)
    assert server._ahead and server.health_report()["runs_ahead"]
    got, steps = _serve(server)
    assert got == want
    if server_kw.get("eos_id", -1) < 0:
        assert [len(o) for o in got] == BUDGETS
    ran_ahead = {kind: sum(1 for k, ahead, _ in steps if ahead and k == kind)
                 for kind in KINDS}
    added = {k: v - before[k] for k, v in _ahead_counts(name).items()}
    assert added == ran_ahead
    assert server._steps_ahead == sum(ran_ahead.values()) > 0
    # a greedy server that prefills in chunks lets them ride its decode
    # steps, through one program too, on a dense model, on a latent routed
    # one and on one with conv layers and per-head routed experts; those of a
    # model with a recurrent or delta-rule state a slot alternate
    fuses = (model_kw is DENSE or model_kw is ROUTED or model_kw is CONV
             ) and server_kw.get("prefill_chunk", 4) > 0
    assert server._fuses == fuses and (ran_ahead["fused"] > 0) == fuses
    assert server._fused is None or server._fused.jitted._cache_size() == 1
    # most steps find the queue occupied: the rest are cold (each program's
    # first run) or follow a step nothing could be enqueued behind
    if not fuses:
        assert sum(ran_ahead.values()) >= 0.6 * len(steps)
    # where chunks ride there is a fourth program to run cold, over fewer
    # steps (a chunk that rides is no step of its own), so a share of the
    # steps says less: EVERY step that found the queue empty is accounted
    # for. It was its program's first run (lockstep, under the first-compile
    # budget), or stood behind a step that ran in lockstep and left nothing
    # in flight, or behind a seam: the one lane of the step in flight
    # finishes with it, the loop lands it and looks again (one, or none, in
    # each case here)
    lockstep = {i for i, (_, ahead, _) in enumerate(steps) if not ahead}
    kinds = [kind for kind, _, _ in steps]
    cold = {kinds.index(kind) for kind in set(kinds)}
    seams = {i for i in lockstep - cold if i - 1 not in lockstep}
    assert cold <= lockstep and len(seams) <= 2, (sorted(lockstep), steps)
    # no new compiled program: one decode program, whatever fed its lanes
    assert server._decode.jitted._cache_size() == 1
    assert server._chunk.jitted._cache_size() <= 1


def test_every_seam_is_crossed_ahead():
    """Dense greedy, chunked, six prompts on two slots: a decode step behind
    a chunk and a chunk behind a decode step, a step behind a prompt's last
    chunk (whose lane joins one step later), a decode step behind a decode
    step, an admission into a freed slot while a step is in flight, and a
    lane masked out of the step behind the one that exhausts its budget.
    The seams of a server that ALTERNATES chunks and decode steps (every
    model whose chunks do not ride: a routed one here; the fused step's
    seams are ``tests/test_fused_step.py``'s)."""
    server = _server(ALTERNATING)
    assert not server._fuses
    masks = []
    real = server._decode

    def spy(packed, *a):
        tok, lens, act = (np.asarray(packed)[i * 2:(i + 1) * 2] for i in range(3))
        masks.append((tok.copy(), act.copy()))
        return real(packed, *a)

    server._decode = spy
    admitted_behind = []
    admit = server._admit_one

    async def admit_spy(slot, req, pages, shared):
        admitted_behind.append(server._pipeline is not None)
        return await admit(slot, req, pages, shared)

    server._admit_one = admit_spy
    outs, steps = _serve(server)
    assert [len(o) for o in outs] == BUDGETS
    pairs = {(a[0], a[2], b[0]) for a, b in zip(steps, steps[1:]) if b[1]}
    # (kind in flight, was it fetched, kind enqueued behind it)
    assert ("chunk", False, "decode") in pairs
    assert ("decode", True, "chunk") in pairs
    assert ("chunk", True, "decode") in pairs or ("chunk", True, "chunk") in pairs
    assert ("decode", True, "decode") in pairs
    assert any(admitted_behind)  # a warm chunked admission needs no drain
    # a decode step behind a decode step takes its lanes' tokens on the device
    assert any((tok[act != 0] == -1).any() for tok, act in masks)
    # a lane whose budget the step in flight exhausts rides no further: no
    # request got a token past its budget, and no step ran with no lane
    assert all(act.any() for _, act in masks)


# -- where it is not exact, lockstep ----------------------------------------------------


@pytest.mark.parametrize("model_kw,server_kw", [
    (DENSE, dict(temperature=1.2, top_k=8, seed=42, eos_id=57)),
    (DENSE, dict(temperature=1.2, top_k=8, seed=42)),
    (DENSE, dict(speculative_tokens=2)),
    (HYBRID, dict(prefill_chunk=8, eos_id=57)),
    (CONV, dict(prefill_chunk=8, eos_id=57)),
    (GDN, dict(prefill_chunk=8, eos_id=57)),
    (HYBRID, dict(prefill_chunk=8, dispatch_depth=1)),
    (SWITCH, dict()),
    (DENSE, dict(dispatch_depth=1)),
], ids=["sampling-live-eos", "sampling", "speculative",
        "recurrent-state-live-eos", "conv-windows-live-eos",
        "delta-rule-state-live-eos", "recurrent-state-depth1",
        "switch-capacity", "depth1"])
def test_falls_back_to_lockstep_where_running_ahead_is_not_exact(
        model_kw, server_kw):
    """Sampling (a lane that joins decode one step later would draw from
    another step's key; with a live EOS a dead lane would consume one),
    speculation, a state a slot of any kind under a live EOS (a lane riding
    one step too long would advance a finished slot's state), the
    capacity-based Switch block, and ``dispatch_depth: 1``: no step is
    enqueued ahead, the counter stays where it was, and the tokens are
    lockstep's."""
    name = "lockstep-" + "-".join(f"{k}{v}" for k, v in sorted(server_kw.items()))
    want, _ = _serve(_server(model_kw, **{**server_kw, "dispatch_depth": 1}))
    before = _ahead_counts(name)
    server = _server(model_kw, name=name, **server_kw)
    assert not server._ahead and not server.health_report()["runs_ahead"]
    got, steps = _serve(server)
    assert got == want
    assert not any(ahead for _, ahead, _ in steps)
    assert server._steps_ahead == 0 and _ahead_counts(name) == before


def _state_books(server) -> dict:
    """The host's counters of what advanced a state and what a step carried
    past it, and of the rows a first chunk reset."""
    books = {(kind, what): c.value for kind, pair in server.m_ssm.items()
             for what, c in zip(("tokens", "masked"), pair)}
    return {**books, "resets": server.m_ssm_resets.value}


@pytest.mark.parametrize("server_kw", [
    dict(), dict(decode_kernel="paged", kernel_interpret=True)],
    ids=["gather", "paged"])
@pytest.mark.parametrize("kind", list(STATE_KINDS))
def test_running_ahead_leaves_the_states_lockstep_leaves(kind, server_kw):
    """Six prompts on two slots (slots handed on, budgets that end on
    different steps, prompts of one chunk and of three): once the loop has
    drained, every slot's row of the state pool holds the same tenant and
    the same state (and window) as the ``dispatch_depth: 1`` server's, bit
    for bit — a lane masked out of the step behind its budget's end leaves
    its row alone, a slot's next tenant resets it behind that step, and a
    lane that joins decode one step later has seen the same tokens. The
    host's books agree: what advanced a state, what a chunk padded, the
    resets. Idle lanes a decode step carried are counted a step: running
    ahead makes the same steps or a few more or fewer (a lane joins one
    step later), so that count may differ by the steps' difference."""
    model_kw = STATE_KINDS[kind]
    name = f"state-{kind}-" + "-".join(sorted(server_kw))
    ref = _server(model_kw, name=name + "-lockstep", prefill_chunk=8,
                  dispatch_depth=1, **server_kw)
    server = _server(model_kw, name=name, prefill_chunk=8, **server_kw)
    assert server._stateful and server._ahead and not ref._ahead
    want, ref_steps = _serve(ref)
    got, steps = _serve(server)
    assert got == want and [len(o) for o in got] == BUDGETS
    assert server._steps_ahead > 0 and ref._steps_ahead == 0
    for slot in range(2):
        a, b = server.slot_state(slot), ref.slot_state(slot)
        assert a["tenancy"] == b["tenancy"] >= 2          # the slot was handed on
        assert a["prompt"] == b["prompt"] and a["tokens"] == b["tokens"]
        for leaf in [k for k in ("state", "window") if k in b]:
            np.testing.assert_array_equal(np.asarray(a[leaf], np.float32),
                                          np.asarray(b[leaf], np.float32))
    books, ref_books = _state_books(server), _state_books(ref)
    idle = ("decode", "masked")
    assert {k: v for k, v in books.items() if k != idle} == {
        k: v for k, v in ref_books.items() if k != idle}
    assert books["decode", "tokens"] == sum(n - 1 for n in BUDGETS)
    assert books["resets"] == len(PROMPTS)
    # (a decode step that carried a chunk — the conv kind's — is one too)
    decodes = [sum(1 for k, _, _ in run if k in ("decode", "fused"))
               for run in (steps, ref_steps)]
    assert books[idle] - ref_books[idle] == 2 * (decodes[0] - decodes[1])


@pytest.mark.parametrize("cell", [
    "falconh1_l4.chat_backlog", "lfm2_l12.draft_backlog",
    "qwen3next_l8.report_backlog"])
def test_the_benchmark_s_cells_with_a_state_run_ahead(cell):
    """The three cells whose models carry a state a slot, as their files
    configure the server (the rehearsal overlay: tiny widths, the same
    options): greedy, ``eos_id`` -1, the default ``dispatch_depth``, and
    routed droplessly where routed at all (``num_experts``, the Switch
    block's, is 0), so the server runs ahead, and says so."""
    from arkflow_tpu.components import (Resource, build_component,
                                        ensure_plugins_loaded)

    sys.path.insert(0, ROOT)
    from benchmark import run as br

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        _, conf = br.lookup(json.load(f), cell)
    with open(os.path.join(ROOT, conf["file"])) as f:
        eng, _ = br.build_engine_mapping(json.load(f), 3000000019, True)
    proc = eng["streams"][0]["pipeline"]["processors"][0]
    assert proc["eos_id"] == -1 and "dispatch_depth" not in proc
    ensure_plugins_loaded()
    server = build_component("processor", proc, Resource())._server
    assert server._stateful and not server.cfg.num_experts
    assert server._ahead and server.health_report()["runs_ahead"]
    longest = int(proc["max_input"])
    prompts = [[1 + i % 100 for i in range(n)]
               for n in (longest, 5, longest // 2)]

    async def go():
        outs = await asyncio.gather(*[server.generate(p, 5) for p in prompts])
        await server.close()
        return outs

    outs = asyncio.run(asyncio.wait_for(go(), timeout=180))
    assert [len(o) for o in outs] == [5] * 3 and server._steps_ahead > 0


def test_cold_programs_and_page_pressure_run_in_lockstep():
    """Each program's first run compiles under the first-compile budget
    with nothing queued before it; a decode step the page pool cannot cover
    is lockstep's (truncation policy) — and every page comes home."""
    cfg, params = _model(DENSE)
    # 7 usable pages; two slots decoding to max_seq need 12: the pool runs
    # dry mid-wave
    server = GenerationServer(params, cfg, slots=2, page_size=4, max_seq=24,
                              num_pages=8, eos_id=-1)
    outs, steps = _serve(server, [[7 + i] for i in range(6)],
                         (3, 20, 5, 20, 2, 20))
    assert all(len(o) >= 1 for o in outs)
    first = {}
    for kind, ahead, _ in steps:
        first.setdefault(kind, ahead)
    assert first == {"prefill": False, "decode": False}
    assert server.m_truncated.value >= 1 or [len(o) for o in outs] == [3, 20, 5, 20, 2, 20]
    assert len(server._free_pages) == server.num_pages - 1 and not server._page_refs
    warm = [ahead for kind, ahead, _ in steps if kind == "decode"][1:]
    assert any(warm) and not all(warm)  # ahead while covered, lockstep when dry


def test_a_prompt_that_stops_after_prefill_exports_with_nothing_in_flight():
    """``prefill_export``: the prompt's last chunk runs in lockstep (its
    pages are fetched off the pools right after), chunks before it may run
    ahead; the export equals a lockstep server's."""
    def export(**kw):
        server = _server(DENSE, **kw)
        seen = []
        real = server._export_and_finish

        async def spy(slot):
            seen.append(server._pipeline)
            await real(slot)

        server._export_and_finish = spy

        async def go():
            warm = await server.generate(list(range(3, 25)), 4)
            out = await server.prefill_export(list(range(30, 52)), 4)
            await server.close()
            return warm, out

        warm, out = asyncio.run(asyncio.wait_for(go(), timeout=120))
        assert seen == [None]
        return warm, out, server._steps_ahead

    warm1, out1, ahead1 = export(dispatch_depth=1)
    warm2, out2, ahead2 = export()
    assert warm1 == warm2 and ahead1 == 0 and ahead2 > 0
    assert out1["first_token"] == out2["first_token"]
    for a, b in zip(out1["k"] + out1["v"], out2["k"] + out2["v"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- two steps in flight: a failure fails both, the ledger stays whole ------------------


@pytest.mark.parametrize("carried", ["chunk", "fused"])
@pytest.mark.parametrize("fault", ["hang", "oom"])
def test_failure_with_a_chunk_and_a_decode_step_in_flight(fault, carried):
    """A deadline miss (a hang consumed by the wait) and an injected step
    failure, landing while a chunk and a decode step are both in flight —
    as two steps (a server that alternates: a routed model's) or with the
    chunk riding a fused step behind another: every request of both steps
    fails (their batches nack upstream), the pools reset with no page
    leaked, nothing stays in flight, and the recovery probe serves the
    exact reference afterwards."""
    cfg, params = _model(DENSE if carried == "fused" else ALTERNATING)

    async def go():
        srv = GenerationServer(
            params, cfg, slots=2, page_size=4, max_seq=48, eos_id=-1,
            prefill_chunk=4, step_deadline_s=0.25, step_deadline_first_s=60.0,
            health_config=HealthConfig(probe_backoff_s=0.05))
        assert srv._fuses == (carried == "fused")
        # warm every program, and the reference
        ref = await asyncio.gather(srv.generate([9, 4], 4),
                                   srv.generate(list(range(3, 25)), 4))
        misses0 = srv.core.m_deadline_miss.value
        ahead0 = srv._steps_ahead
        # budgets that outlast every chunk of the two long prompts, however
        # late this coroutine is scheduled: nobody finishes before the fault
        tasks = [asyncio.ensure_future(srv.generate(p, n)) for p, n in (
            ([9, 4], 44), (list(range(3, 43)), 8), (list(range(40, 80)), 8))]
        armed, run_ahead = [], srv._run_ahead

        def arming(key, *a, **kw):
            # a step enqueued behind a chunk (alone, or riding a decode
            # step) that is still in flight: both are on the device's queue.
            # Armed here, on the loop, and not by a poll that a loaded
            # machine can starve until the chunks are over
            pend = srv._pipeline
            if (not armed and srv._steps_ahead > ahead0 + 2 and pend is not None
                    and pend.kind == carried):
                armed.append(key)
                srv.inject_step_fault(fault, 3.0)
            return run_ahead(key, *a, **kw)

        srv._run_ahead = arming
        results = await asyncio.gather(*tasks, return_exceptions=True)
        assert armed, "never had a chunk in flight ahead"
        if fault == "hang":
            assert all(isinstance(r, StepDeadlineExceeded) for r in results), results
            assert srv.core.m_deadline_miss.value == misses0 + 1
        else:
            assert all(isinstance(r, Exception) and "RESOURCE_EXHAUSTED" in str(r)
                       for r in results), results
        assert srv._pipeline is None and srv._gen_inflight == 0
        assert len(srv._free_pages) == srv.num_pages - 1
        assert not srv._page_refs and not srv._prefill_pos
        out = await asyncio.gather(srv.generate([9, 4], 4),
                                   srv.generate(list(range(3, 25)), 4))
        assert out == ref
        assert srv.core.health.state == "healthy"
        await srv.close()

    asyncio.run(asyncio.wait_for(go(), timeout=180))


def test_a_chunk_nobody_waits_for_hands_its_deadline_to_the_step_behind():
    """A prompt's chunk before its last is not waited for where the step
    behind it is: that step's wait runs from the CHUNK's dispatch stamp, so
    each deadline still counts from its own step's dispatch."""
    server = _server(DENSE)
    stamps = []
    land = server._land

    async def spy(rec, behind=None):
        before = None if behind is None else behind.dispatched_at
        await land(rec, behind)
        if rec.apply is None and behind is not None and behind.apply is not None:
            stamps.append((rec.dispatched_at, before, behind.dispatched_at))

    server._land = spy
    _serve(server)
    assert stamps
    for chunk_at, behind_was, behind_is in stamps:
        assert chunk_at <= behind_was and behind_is == chunk_at


# -- every step is still observed, and the loop's timeline still tiles ------------------


def _stage_sums(before=None):
    sums = {}
    for m in global_registry().collect():
        if m.name == "arkflow_stage_seconds":
            key = m.labels["stage"]
            s, c = sums.get(key, (0.0, 0))
            sums[key] = (s + m.sum, c + m.count)
    if before is None:
        return sums
    return {k: (s - before.get(k, (0.0, 0))[0], c - before.get(k, (0.0, 0))[1])
            for k, (s, c) in sums.items()}


@pytest.mark.parametrize("depth", [1, 2], ids=["lockstep", "ahead"])
def test_the_loops_timeline_still_tiles(depth):
    """The serve loop is one thread: at any moment it is inside a hop
    (``gen_device_wait``: the enqueue's and the wait's), between the loop
    and a hop's thread (``gen_handoff``), or doing its own work
    (``gen_prepare``, ``gen_apply``, ``gen_admit``). Those stages, each
    observed once a step, add up to the loop's wall clock — no phase is
    missing and none is counted twice — with one step in flight as in
    lockstep. In lockstep the loop's own work is the device's idle gap
    (``arkflow_tpu_device_idle_gap_seconds``); one step ahead the device
    works through it, so the gaps shrink below it."""
    from arkflow_tpu.obs.trace import TracingConfig, global_tracer

    global_tracer().configure(TracingConfig())
    name = f"tiles-{depth}"
    server = _server(DENSE, name=name, dispatch_depth=depth)
    # warm every program first: a compile is no part of a steady timeline
    _warm(server)
    gap = global_registry().histogram(
        "arkflow_tpu_device_idle_gap_seconds",
        labels={"model": name, "path": "generate"})
    gap0, before = gap.sum, _stage_sums()

    async def go():
        t0 = time.perf_counter()
        outs = await asyncio.gather(*[
            server.generate(p, 12) for p in PROMPTS * 2])
        return outs, time.perf_counter() - t0

    outs, wall = asyncio.run(asyncio.wait_for(go(), timeout=180))
    added = _stage_sums(before)
    steps = added["gen_device_wait"][1]
    assert steps > 50
    for stage in ("gen_handoff", "gen_prepare", "gen_apply"):
        assert added[stage][1] == steps, (stage, added)
    inside = sum(added[s][0] for s in ("gen_device_wait", "gen_handoff"))
    own = sum(added[s][0] for s in ("gen_prepare", "gen_apply", "gen_admit"))
    assert inside + own <= wall * 1.001           # nothing counted twice
    # nothing missing: what is left is the loop's top and the stamps (tiny
    # steps on the CPU: 0.84-0.92 alone, less under a loaded test run; a
    # hop that went unobserved would leave half)
    assert inside + own >= wall * 0.65, (inside, own, wall)
    gaps = gap.sum - gap0
    # a gap overlaps a hop by the few stamps between them (and by whatever
    # a loaded machine takes the thread away for just there), no more
    assert inside + gaps <= wall * 1.25
    if depth == 1:
        assert gaps >= own * 0.9   # lockstep: the loop's work idles the device
    else:
        assert server._steps_ahead > 0.8 * steps


def _warm(server):
    async def go():
        await asyncio.gather(*[server.generate(p, 3) for p in PROMPTS[:4]])

    asyncio.run(asyncio.wait_for(go(), timeout=120))


# -- the benchmark's reader -------------------------------------------------------------


class _View:
    """``run.py::View``'s two calls over fixed window deltas."""

    def __init__(self, ahead, steps):
        self._ahead, self._steps = ahead, steps

    def counter(self, name, **labels):
        assert name == "arkflow_gen_steps_ahead_total" and not labels
        return self._ahead

    def hist(self, name, **labels):
        assert (name, labels) == ("arkflow_stage_seconds",
                                  {"stage": "gen_device_wait"})
        return 1.0, self._steps


@pytest.mark.parametrize("ahead,steps,want", [
    (90.0, 100.0, 90.0), (4000.0, 4400.0, 4000 / 44), (0.0, 100.0, None),
    (0.0, 0.0, None), (5.0, 0.0, None)],
    ids=["engaged", "a-window", "parent-or-lockstep", "no-steps", "no-divisor"])
def test_gen_steps_ahead_pct_reader(ahead, steps, want):
    """``benchmark/metrics/gen_steps_ahead_pct.py``: the counter over the
    observations of ``gen_device_wait``, in percent; nothing on a program
    without the counter (the parent reads 0 of it) or without steps."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "gen_steps_ahead_pct",
        os.path.join(ROOT, "benchmark", "metrics", "gen_steps_ahead_pct.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    got = mod.read(_View(ahead, steps))
    assert got == want if want is None else got == pytest.approx(want)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == "gen_steps_ahead_pct"]
    assert entry["layer"] == "scheduler, generate"
    assert entry["source"] == "program_counter" and entry["moves"] == "tokens_per_s"
    # the generate cells whose servers ran ahead when the list was written:
    # those with a state a slot (a Mamba mixer, conv windows, a delta rule's
    # matrix state) run ahead since PR 50 and the counter moves there too,
    # but the list is the benchmark's to extend (ROADMAP S8 (b))
    assert set(entry["workloads"]) == {
        w["name"] for w in bench["workloads"]
        if w["config"] not in ("bert-base", "falcon-h1-34b-l4", "lfm2-8b-a1b-l12",
                               "qwen3-next-80b-a3b-l8-ep8")}
