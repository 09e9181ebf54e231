"""One host array in, one out, per generate step (tiny shapes, CPU).

A device step's tokens, lengths, mask and page table go up as ONE packed
int32 array (``pack_operands``), a sampling server's key and a routed
prompt's counters stay on the device, and the step's token array comes back
inside the executor hop. What is served must not change: the expectations
below were recorded at the commit before the packing (PR 29's tree) with
this file's ``_serve``, greedy and sampled alike.
"""

from __future__ import annotations

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arkflow_tpu.models import get_model
from arkflow_tpu.obs import global_registry
from arkflow_tpu.tpu.serving import (GenerationServer, pack_operands,
                                     unpack_operands)

DENSE = dict(vocab_size=128, dim=64, layers=2, heads=4, kv_heads=2, ffn=96,
             max_seq=64)
#: latent attention + top-2 of 8 routed experts behind one dense layer
ROUTED = dict(vocab_size=128, dim=32, layers=3, heads=4, ffn=64, max_seq=128,
              rope_theta=1e4, norm_eps=1e-6, kv_lora_rank=16,
              qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
              rope_interleave=True, n_routed_experts=8, num_experts_per_tok=2,
              n_shared_experts=2, moe_intermediate_size=16,
              first_k_dense_replace=1, routed_scaling_factor=2.448)
PROMPTS = [list(range(3, 25)), [9, 4], list(range(40, 55)), [7]]
SAMPLE = dict(temperature=1.2, top_k=8, seed=42)

DENSE_GREEDY = [[24, 24, 24, 24, 24, 24], [23, 23, 23, 23, 23, 105],
                [99, 99, 57, 99, 57, 65], [96, 96, 96, 96, 96, 96]]
DENSE_SAMPLED_CHUNKED = [[24, 62, 92, 24, 24, 66], [84, 116, 48, 23, 97, 80],
                         [57, 99, 90, 90, 109, 104], [18, 51, 11, 76, 76, 102]]
#: name -> (model, server options, tp, the parent's tokens)
CASES = {
    "dense-greedy-oneshot": (DENSE, {}, 0, DENSE_GREEDY),
    "dense-greedy-chunked": (DENSE, dict(prefill_chunk=4), 0, DENSE_GREEDY),
    "dense-greedy-depth2": (
        DENSE, dict(prefill_chunk=4, dispatch_depth=2), 0, DENSE_GREEDY),
    "dense-greedy-speculative": (
        DENSE, dict(prefill_chunk=4, speculative_tokens=2), 0, DENSE_GREEDY),
    "dense-greedy-prefix": (DENSE, dict(prefix_cache_pages=8), 0, DENSE_GREEDY),
    "dense-greedy-chunked-tp2": (DENSE, dict(prefill_chunk=4), 2, DENSE_GREEDY),
    "dense-greedy-speculative-tp2": (
        DENSE, dict(speculative_tokens=2), 2, DENSE_GREEDY),
    "routed-greedy-chunked": (ROUTED, dict(prefill_chunk=8), 0, [
        [89, 1, 69, 1, 71, 1], [63, 39, 57, 57, 37, 37],
        [126, 70, 126, 126, 126, 126], [76, 76, 116, 116, 9, 54]]),
    "dense-sample-oneshot": (DENSE, SAMPLE, 0, [
        [46, 110, 75, 110, 19, 24], [23, 33, 23, 33, 84, 52],
        [64, 123, 2, 114, 57, 15], [96, 81, 127, 40, 22, 51]]),
    "dense-sample-chunked": (
        DENSE, dict(prefill_chunk=4, **SAMPLE), 0, DENSE_SAMPLED_CHUNKED),
    "dense-sample-chunked-tp2": (
        DENSE, dict(prefill_chunk=4, **SAMPLE), 2, DENSE_SAMPLED_CHUNKED),
    "dense-sample-notopk": (
        DENSE, dict(prefill_chunk=4, temperature=0.7, seed=7), 0, [
            [52, 26, 110, 11, 76, 9], [64, 11, 23, 122, 58, 94],
            [15, 57, 90, 112, 33, 22], [76, 83, 11, 105, 101, 118]]),
    "routed-sample-chunked": (ROUTED, dict(prefill_chunk=8, **SAMPLE), 0, [
        [127, 67, 69, 71, 118, 67], [103, 94, 122, 32, 104, 21],
        [126, 104, 39, 45, 104, 45], [22, 115, 66, 105, 110, 54]]),
    "routed-sample-oneshot": (ROUTED, SAMPLE, 0, [
        [127, 96, 127, 109, 69, 71], [103, 122, 20, 20, 41, 103],
        [70, 80, 70, 73, 34, 47], [22, 115, 115, 116, 115, 6]]),
}


def _server(model_kw, server_kw, tp, name="decoder_lm"):
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**model_kw)
    params = fam.init(jax.random.PRNGKey(11), cfg)
    mesh = None
    if tp:
        if len(jax.devices()) < tp:
            pytest.skip(f"needs {tp} virtual devices")
        from arkflow_tpu.parallel.mesh import (MeshSpec, create_mesh,
                                               shard_params)

        mesh = create_mesh(MeshSpec(tp=tp), devices=jax.devices()[:tp])
        axes = {n: n for n in mesh.axis_names}
        params = shard_params(params, fam.param_specs(cfg, axes), mesh)
    return GenerationServer(params, cfg, slots=2, page_size=4, max_seq=48,
                            eos_id=-1, mesh=mesh, name=name, **server_kw)


def _serve(server, new=6):
    async def go():
        outs = await asyncio.gather(*[server.generate(p, new) for p in PROMPTS])
        await server.close()
        return outs

    return asyncio.run(go())


KINDS = ("decode", "chunk", "prefill", "verify", "fused")


def _counts(name):
    reg = global_registry()
    return {kind: reg.counter("arkflow_gen_step_uploads_total",
                              labels={"model": name, "kind": kind}).value
            for kind in KINDS}


# -- the layout ------------------------------------------------------------------------


@pytest.mark.parametrize("kind,rows,width", [
    ("decode", 4, 1), ("verify", 4, 3), ("chunk", 1, 8), ("prefill", 1, 32)])
@pytest.mark.parametrize("tp", [0, 2], ids=["one-device", "tp2"])
def test_packed_layout_round_trips(kind, rows, width, tp):
    """What the host packs is what the program slices apart, part for part,
    for every step kind's shapes — jitted, and placed replicated over a mesh
    the way a tensor-parallel server's steps take it."""
    pages = 5
    rng = np.random.RandomState(rows * 31 + width)
    ids = rng.randint(0, 1000, (rows, width))
    a, b = rng.randint(0, 48, rows), rng.randint(0, 2, rows)
    table = rng.randint(0, 99, (rows, pages))
    packed = pack_operands(ids, a, b, table)
    assert packed.dtype == np.int32
    assert packed.shape == (rows * (width + 2 + pages),)
    kw = {}
    if tp:
        if len(jax.devices()) < tp:
            pytest.skip(f"needs {tp} virtual devices")
        from arkflow_tpu.parallel.mesh import (MeshSpec, create_mesh,
                                               replicated)

        mesh = create_mesh(MeshSpec(tp=tp), devices=jax.devices()[:tp])
        kw = dict(in_shardings=(replicated(mesh),),
                  out_shardings=replicated(mesh))
    got = jax.jit(lambda p: unpack_operands(p, rows, pages), **kw)(packed)
    for part, want in zip(got, (ids, a, b, table)):
        assert part.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(part), want)


@pytest.mark.parametrize("state", [0, 1], ids=["pages-only", "state-column"])
def test_a_fused_step_s_packed_array_is_both_steps_end_to_end(state):
    """A fused step's ONE array is the decode step's packed operands and then
    the chunk's; of a server whose model caches a state a slot, a table row
    of EITHER part ends with the slot's row of the state pool (``state`` 1:
    the lanes' column is dropped — a lane's row follows from its index — and
    the chunk's is handed on as a fifth part, as ``_chunk`` takes it)."""
    slots, pages, c = 3, 5, 4
    rng = np.random.RandomState(7 + state)
    tok, lens, act = rng.randint(0, 99, slots), rng.randint(0, 40, slots), [1, 0, 1]
    table = rng.randint(1, 99, (slots, pages + state))
    ids, row = rng.randint(0, 99, c), rng.randint(1, 99, (1, pages + state))
    if state:
        table[:, -1], row[:, -1] = np.arange(slots) + 1, 2   # slot 1 prefills
    packed = np.concatenate([pack_operands(tok, lens, act, table),
                             pack_operands(ids, 8, 3, row)])
    lanes = slots * (3 + pages + state)
    assert packed.shape == (lanes + c + 2 + pages + state,)

    @jax.jit
    def apart(p):
        return (unpack_operands(p[:lanes], slots, pages, state=state)[:4],
                unpack_operands(p[lanes:], 1, pages, state=state))

    (t, n, a, tab), (i, off, clen, its, *held) = apart(packed)
    np.testing.assert_array_equal(np.asarray(t)[:, 0], tok)
    np.testing.assert_array_equal(np.asarray(n), lens)
    np.testing.assert_array_equal(np.asarray(a), act)
    np.testing.assert_array_equal(np.asarray(tab), table[:, :pages])
    np.testing.assert_array_equal(np.asarray(i), ids[None])
    assert (int(off[0]), int(clen[0])) == (8, 3)
    np.testing.assert_array_equal(np.asarray(its), row[:, :pages])
    assert [np.asarray(h).tolist() for h in held] == ([[2]] if state else [])


def test_a_stateful_server_packs_both_parts_state_columns():
    """The array a server of the LFM2 layout hands its ``_fused`` program:
    the lanes' table rows end with rows 1..slots of the state pool and the
    riding chunk's with its own slot's (``_table``), so the program is told
    both parts' rows in the one upload."""
    from tests.test_fused_step import CONV_ROUTED

    fam = get_model("decoder_lm")
    cfg = fam.make_config(**CONV_ROUTED)
    server = GenerationServer(fam.init(jax.random.PRNGKey(11), cfg), cfg, slots=3,
                              page_size=4, max_seq=48, eos_id=-1, prefill_chunk=4,
                              name="fused-state-columns")
    assert server._stateful and server._fuses
    seen, real = [], server._fused

    def spy(packed, *a):
        seen.append(np.asarray(packed))
        return real(packed, *a)

    server._fused = spy

    async def go():
        outs = await asyncio.gather(server.generate(list(range(3, 25)), 6),
                                    server.generate(list(range(40, 55)), 5))
        await server.close()
        return outs

    assert [len(o) for o in asyncio.run(go())] == [6, 5]
    cols = server.pages_per_slot + 1
    lanes = 3 * (3 + cols)
    assert seen and {p.shape for p in seen} == {(lanes + 4 + 2 + cols,)}
    for packed in seen:
        table = packed[3 * 3:lanes].reshape(3, cols)
        np.testing.assert_array_equal(table[:, -1], [1, 2, 3])
        act = packed[6:9]
        riding = int(packed[-1]) - 1            # the chunk's slot, by its row
        assert riding in (0, 1) and not act[riding] and act.any()


def test_packed_scalars_and_bools_flatten_in_order():
    """A chunk's offset and length are scalars and a decode mask is bool:
    each part lands as int32 where the layout says."""
    packed = pack_operands(np.arange(3), 7, 2, np.zeros((1, 2)))
    np.testing.assert_array_equal(packed, [0, 1, 2, 7, 2, 0, 0])
    packed = pack_operands([5, 6], [1, 2], np.array([True, False]),
                           [[3], [4]])
    np.testing.assert_array_equal(packed, [5, 6, 1, 2, 1, 0, 3, 4])


# -- what is served is what was served -------------------------------------------------


@pytest.mark.parametrize("case", sorted(CASES))
def test_served_tokens_are_the_parents(case):
    """Greedy and sampled (fixed seed) tokens of every step-kind mix — dense
    and routed, one device and tp=2 — equal the tokens recorded before the
    operands were packed and the key moved onto the device."""
    model_kw, server_kw, tp, want = CASES[case]
    assert _serve(_server(model_kw, server_kw, tp)) == want


# -- one upload a step, no key traffic -------------------------------------------------


@pytest.mark.parametrize("model_kw,server_kw,kinds", [
    (DENSE, dict(prefill_chunk=4), {"decode", "chunk", "prefill", "fused"}),
    (DENSE, dict(prefill_chunk=4, dispatch_depth=2),
     {"decode", "chunk", "prefill", "fused"}),
    (DENSE, dict(prefill_chunk=4, speculative_tokens=2),
     {"verify", "chunk", "prefill"}),
    (DENSE, dict(prefill_chunk=4, **SAMPLE), {"decode", "chunk", "prefill"}),
    (ROUTED, dict(prefill_chunk=8), {"decode", "fused", "prefill"}),
    (ROUTED, dict(prefill_chunk=8, **SAMPLE), {"decode", "chunk", "prefill"}),
], ids=["dense", "depth2", "speculative", "sampled", "routed",
        "routed-sampled"])
def test_every_step_hands_the_device_one_host_array(model_kw, server_kw, kinds):
    """``arkflow_gen_step_uploads_total{kind}`` over the steps of that kind
    is 1.0 for every kind that ran — a routed prompt's first chunk (whose
    counters start from a device constant), a depth-2 step fed by the step
    before and a decode step that carries a chunk (a greedy server's chunks
    ride; a latent routed model's all do here, its counters so far handed on
    on the device) included; everything else a step takes is on the
    device."""
    name = "uploads-" + "-".join(f"{k}{v}" for k, v in sorted(server_kw.items()))
    name += "-routed" if model_kw is ROUTED else ""
    server = _server(model_kw, server_kw, 0, name=name)
    steps = dict.fromkeys(KINDS, 0)
    host_arrays = []
    for kind in steps:
        def counted(*args, _fn=getattr(server, "_" + kind), _kind=kind):
            steps[_kind] += 1
            host_arrays.append(sum(isinstance(a, np.ndarray) for a in args))
            assert all(isinstance(a, (np.ndarray, jax.Array)) for a in args)
            return _fn(*args)

        setattr(server, "_" + kind, counted)
    before = _counts(name)
    outs = _serve(server)
    assert [len(o) for o in outs] == [6] * len(PROMPTS)
    uploads = {k: v - before[k] for k, v in _counts(name).items()}
    assert {k for k, n in steps.items() if n} == kinds
    assert uploads == steps  # 1.0 a step, kind by kind
    assert set(host_arrays) == {1}
    if server_kw.get("dispatch_depth", 1) > 1:
        assert server._steps_ahead > 0


def test_greedy_server_never_splits_a_key(monkeypatch):
    """temperature 0 reads no key: the greedy programs take none, and
    nothing on the serve loop dispatches a ``random.split``."""
    calls = {"split": 0}
    real = jax.random.split

    def counting(*a, **kw):
        calls["split"] += 1
        return real(*a, **kw)

    server = _server(DENSE, dict(prefill_chunk=4), 0)
    monkeypatch.setattr(jax.random, "split", counting)
    assert _serve(server) == DENSE_GREEDY
    assert calls["split"] == 0 and server._key is None


def test_sampling_server_splits_inside_its_programs(monkeypatch):
    """A sampling server's key stays on the device: the split happens while
    a step's program is traced (once a program), never eagerly a step."""
    calls = {"split": 0}
    real = jax.random.split

    def counting(key, *a, **kw):
        calls["split"] += not isinstance(key, jax.core.Tracer)
        return real(key, *a, **kw)

    server = _server(DENSE, dict(prefill_chunk=4, **SAMPLE), 0)
    key0 = np.asarray(server._key)
    monkeypatch.setattr(jax.random, "split", counting)
    assert _serve(server) == DENSE_SAMPLED_CHUNKED
    assert calls["split"] == 0
    assert isinstance(server._key, jax.Array)
    assert not np.array_equal(np.asarray(server._key), key0)


def test_step_tokens_reach_the_loop_as_numpy():
    """The fetch happens inside the executor hop: the coroutine resumes with
    a host array (``gen_apply`` makes no device call); a prompt's chunk
    before its last leaves its array on the device — and so does the
    lockstep decode step that carries a routed prompt's chunk: the prompt's
    counters ride on in that array, and ``_step`` reads the step's tokens
    out of it (the array is ready: a copy, stage ``gen_fetch``)."""
    server = _server(ROUTED, dict(prefill_chunk=8), 0)
    seen = []
    run = server._run_device_step

    async def spying(key, *a, **kw):
        out = await run(key, *a, **kw)
        seen.append((key[0], kw.get("final", True), type(out)))
        return out

    server._run_device_step = spying
    _serve(server)
    assert {k for k, _, _ in seen} == {"decode", "fused", "prefill"}
    assert {final for k, final, _ in seen if k == "fused"} == {False}
    for kind, final, typ in seen:
        assert issubclass(typ, np.ndarray if final else jax.Array), (kind, final)
    assert any(not final for _, final, _ in seen)
