"""The generate path's placed param tree (``TpuGenerateProcessor._place_params``
+ ``decoder.serve_dtypes``): weights live on the device in the dtype the
generation programs multiply in, so no compiled step casts a parameter; norm
scales and the MoE router stay float32; ``host_params`` stays the float32
masters; sharding, hot swap and the integrity repair keep the placed form."""

from __future__ import annotations

import asyncio
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded
from arkflow_tpu.models.decoder import generate
from arkflow_tpu.models.paged_decode import (init_page_pool, paged_decode_step,
                                             paged_prefill, paged_prefill_chunk)
from arkflow_tpu.obs import global_registry

ensure_plugins_loaded()

TINY = {"vocab_size": 128, "dim": 16, "layers": 2, "heads": 2, "kv_heads": 2,
        "ffn": 32, "max_seq": 64}
MODELS = {"dense": TINY, "moe": {**TINY, "num_experts": 4}}
PAGE = 4


def _proc(model_config, **extra):
    cfg = {"type": "tpu_generate", "model": "decoder_lm",
           "model_config": model_config, "max_input": 16, "max_new_tokens": 4,
           "batch_buckets": [2], "seq_buckets": [16], **extra}
    return build_component("processor", cfg, Resource())


@pytest.fixture(scope="module", params=sorted(MODELS))
def placed(request):
    """(processor, float32 host tree, placed tree) of a batch-mode processor."""
    proc = _proc(MODELS[request.param])
    return proc, proc.host_params, proc.params


def _leaf_dtypes(tree) -> dict[str, str]:
    return {jax.tree_util.keystr(path): str(leaf.dtype)
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _is_float32_consumed(path: str) -> bool:
    return "'scale'" in path or "'router'" in path


def _step_inputs(cfg):
    """Two slots mid-generation on non-contiguous pages, plus a 3-token chunk."""
    kp, vp = init_page_pool(cfg, 9, PAGE)
    table = jnp.asarray([[1, 3, 5, 7], [2, 4, 6, 8]], jnp.int32)
    ids = jnp.asarray(np.random.RandomState(5).randint(1, cfg.vocab_size, (2, 6)),
                      jnp.int32)
    lens = jnp.asarray([6, 4], jnp.int32)
    return kp, vp, table, ids, lens


STEPS = {
    "prefill": lambda p, cfg, kp, vp, table, ids, lens: paged_prefill(
        p, cfg, ids, lens, table, kp, vp, return_logits=True),
    "decode": lambda p, cfg, kp, vp, table, ids, lens: paged_decode_step(
        p, cfg, ids[:, 0], lens, jnp.asarray([True, True]), table, kp, vp,
        return_logits=True),
    "chunk": lambda p, cfg, kp, vp, table, ids, lens: paged_prefill_chunk(
        p, cfg, ids[:, :3], lens, jnp.asarray([3, 2], jnp.int32), table, kp, vp,
        return_all=True),
    "generate": lambda p, cfg, kp, vp, table, ids, lens: generate(
        p, cfg, ids, lens, 3, eos_id=-1),
}


# -- (b) which leaves are what ------------------------------------------------


def test_placed_tree_dtypes_and_float32_masters(placed):
    proc, host, tree = placed
    assert set(_leaf_dtypes(host).values()) == {"float32"}
    got = _leaf_dtypes(tree)
    assert got.keys() == _leaf_dtypes(host).keys()
    for path, dtype in got.items():
        want = "float32" if _is_float32_consumed(path) else "bfloat16"
        assert dtype == want, (path, dtype)
    assert any("'router'" in p for p in got) == (proc.cfg.num_experts > 1)
    # the cast is round-to-nearest-even of the master, as cm.dense does at use
    for h, t in zip(jax.tree_util.tree_leaves(host),
                    jax.tree_util.tree_leaves(tree)):
        np.testing.assert_array_equal(np.asarray(h.astype(t.dtype)), np.asarray(t))


def test_placed_bytes_gauge_reads_the_tree(placed):
    _, _, tree = placed
    by_dtype: dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        by_dtype[str(leaf.dtype)] = by_dtype.get(str(leaf.dtype), 0) + leaf.nbytes
    assert set(by_dtype) == {"bfloat16", "float32"}
    for dtype, nbytes in by_dtype.items():
        gauge = global_registry().gauge(
            "arkflow_gen_param_bytes", labels={"model": "decoder_lm", "dtype": dtype})
        assert gauge.value == nbytes


# -- (a) the same arithmetic ---------------------------------------------------


@pytest.mark.parametrize("step", ["prefill", "decode", "chunk"])
def test_step_logits_equal_on_placed_and_float32_tree(placed, step):
    proc, host, tree = placed
    fn = jax.jit(lambda p, *a: STEPS[step](p, proc.cfg, *a))
    inputs = _step_inputs(proc.cfg)
    want = fn(host, *inputs)
    got = fn(tree, *inputs)
    for w, g in zip(want, got):  # logits, then both KV pools
        np.testing.assert_array_equal(np.asarray(w), np.asarray(g))


def test_greedy_tokens_equal_over_16_steps(placed):
    proc, host, tree = placed
    ids = jnp.asarray(np.random.RandomState(7).randint(1, 128, (2, 8)), jnp.int32)
    lens = jnp.asarray([8, 5], jnp.int32)
    gen = jax.jit(lambda p: generate(p, proc.cfg, ids, lens, 16, eos_id=-1))
    want, got = gen(host), gen(tree)
    np.testing.assert_array_equal(np.asarray(want[0]), np.asarray(got[0]))
    assert int(np.asarray(got[1]).min()) == 16


# -- (c) no compiled step casts a parameter ------------------------------------

#: ops that hand a parameter on whole, in another layout
_LAYOUT_OPS = {"reshape", "transpose", "squeeze", "slice", "dynamic_slice",
               "broadcast_in_dim", "copy"}


def _sub_jaxprs(eqn):
    """(inner jaxpr, the eqn's operands that feed its invars, in order)."""
    p = eqn.params
    if eqn.primitive.name == "cond":
        return [(b.jaxpr, eqn.invars[1:]) for b in p["branches"]]
    if eqn.primitive.name == "while":
        nc, nb = p["cond_nconsts"], p["body_nconsts"]
        carry = eqn.invars[nc + nb:]
        return [(p["cond_jaxpr"].jaxpr, eqn.invars[:nc] + carry),
                (p["body_jaxpr"].jaxpr, eqn.invars[nc:nc + nb] + carry)]
    out = []
    for v in p.values():
        inner = getattr(v, "jaxpr", v)
        if hasattr(inner, "eqns") and len(inner.invars) == len(eqn.invars):
            out.append((inner, eqn.invars))
    return out


def param_casts(jaxpr, param_vars) -> list[str]:
    """float32 -> bfloat16 ``convert_element_type`` eqns whose operand is a
    program parameter (or a slice / reshape of one), nested scans, loops,
    branches and calls included."""
    found = []
    params = set(param_vars)
    for eqn in jaxpr.eqns:
        from_param = [v for v in eqn.invars
                      if not isinstance(v, Literal) and v in params]
        name = eqn.primitive.name
        if name == "convert_element_type" and from_param:
            src = from_param[0].aval
            if src.dtype == jnp.float32 and eqn.params["new_dtype"] == jnp.bfloat16:
                found.append(f"{src.shape}")
        elif name in _LAYOUT_OPS and from_param and eqn.invars[0] is from_param[0]:
            params.update(eqn.outvars)
        for inner, operands in _sub_jaxprs(eqn):
            inner_params = [iv for iv, ov in zip(inner.invars, operands)
                            if not isinstance(ov, Literal) and ov in params]
            found += param_casts(inner, inner_params)
    return found


def _casts_of(step, cfg, tree):
    inputs = _step_inputs(cfg)
    closed = jax.make_jaxpr(lambda p: STEPS[step](p, cfg, *inputs))(tree)
    n_params = len(jax.tree_util.tree_leaves(tree))
    return param_casts(closed.jaxpr, closed.jaxpr.invars[:n_params])


@pytest.mark.parametrize("step", sorted(STEPS))
def test_no_step_casts_a_placed_parameter(placed, step):
    """Fails when a layer added to the forward casts a leaf that
    ``serve_dtypes`` does not place in the dtype it is consumed in."""
    proc, host, tree = placed
    assert _casts_of(step, proc.cfg, tree) == []
    # the walk has teeth: on the float32 masters every weight leaf is cast
    n_weights = sum(d == "bfloat16" for d in _leaf_dtypes(tree).values())
    assert len(_casts_of(step, proc.cfg, host)) >= n_weights


# -- (d) sharding --------------------------------------------------------------


@pytest.mark.parametrize("model", sorted(MODELS))
def test_placed_leaves_keep_param_specs_sharding_under_a_mesh(model):
    from jax.sharding import NamedSharding

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    proc = _proc(MODELS[model], mesh={"tp": 2})
    axes = {name: name for name in proc.mesh.axis_names}
    specs = proc.family.param_specs(proc.cfg, axes)
    dtypes = proc.family.extras["serve_dtypes"](proc.cfg)

    def check(leaf, spec, dtype):
        want = NamedSharding(proc.mesh, spec)
        assert isinstance(leaf.sharding, NamedSharding)
        assert leaf.sharding.is_equivalent_to(want, leaf.ndim), (leaf.sharding, want)
        assert leaf.dtype == dtype
        return leaf

    jax.tree_util.tree_map(check, proc.params, specs, dtypes)
    wq = proc.params["layers"]["wq"]["w"]
    assert len({s.device for s in wq.addressable_shards}) == 2
    assert wq.addressable_shards[0].data.shape[-1] == wq.shape[-1] // 2


# -- (e) hot swap, bitflip -> detect -> repair ---------------------------------


@pytest.mark.parametrize("model", sorted(MODELS))
def test_swap_and_integrity_repair_end_with_a_placed_tree(model, tmp_path):
    from arkflow_tpu.tpu import checkpoint
    from arkflow_tpu.tpu.runner import init_host_params

    proc = _proc(MODELS[model], serving="continuous", slots=2, page_size=PAGE,
                 swap={"canary": {"min_agreement": 0.0}},
                 integrity={"probe_interval": "999s", "digest_every": 1})
    mon, srv = proc.integrity, proc._server
    boot_dtypes = _leaf_dtypes(proc.params)
    assert "bfloat16" in boot_dtypes.values()

    async def go():
        rep = await mon.probe_now()
        assert rep["ok"] == 1 and rep["mismatches"] == 0, rep

        new_host = init_host_params(proc.family, proc.cfg, 42)
        ck = str(tmp_path / "ck42")
        checkpoint.save(ck, new_host)  # checkpoints are float32 masters
        srep = await proc.swapper.swap(ck)
        assert srep["version"] == 1, srep
        assert proc.params is srv.params
        assert _leaf_dtypes(proc.params) == boot_dtypes
        assert set(_leaf_dtypes(proc.host_params).values()) == {"float32"}
        rep = await mon.probe_now()
        assert rep["ok"] == 1 and rep["mismatches"] == 0, rep

        srv.inject_step_fault("bitflip")
        assert _leaf_dtypes(srv.params) == boot_dtypes  # garbled in its own dtype
        rep = await mon.probe_now()
        assert rep["mismatches"] == 1 and rep["repaired"] == 1, rep
        assert proc.params is srv.params
        assert _leaf_dtypes(proc.params) == boot_dtypes
        for h, t in zip(jax.tree_util.tree_leaves(new_host),
                        jax.tree_util.tree_leaves(proc.params)):
            np.testing.assert_array_equal(  # repaired to the swapped masters
                np.asarray(h.astype(t.dtype)), np.asarray(t))
        rep = await mon.probe_now()
        assert rep["ok"] == 1 and rep["mismatches"] == 0, rep
        out = await srv.generate([3, 5, 7], max_new_tokens=3)
        assert len(out) == 3
        await srv.close()

    asyncio.run(asyncio.wait_for(go(), timeout=300))


def test_a_large_leaf_placed_in_pieces_is_the_leaf_placed_whole(monkeypatch):
    """Over ``_PIECES_OVER`` bytes a float32 master goes to the device an
    index of its leading axis at a time: the placed tree is, bit for bit and
    dtype for dtype, the one placed whole."""
    from arkflow_tpu.plugins.processor import tpu_generate as tg

    proc = _proc(MODELS["moe"])
    whole = proc.params
    pieces = []
    real = tg._put_in_pieces
    monkeypatch.setattr(tg, "_PIECES_OVER", 1024)
    monkeypatch.setattr(tg, "_put_in_pieces", lambda leaf, *a: (
        pieces.append(leaf.shape), real(leaf, *a))[1])
    again = proc._place_params(proc.host_params)
    assert pieces and all(len(s) > 1 for s in pieces)
    assert _leaf_dtypes(again) == _leaf_dtypes(whole)
    for a, b in zip(jax.tree_util.tree_leaves(again),
                    jax.tree_util.tree_leaves(whole)):
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))
