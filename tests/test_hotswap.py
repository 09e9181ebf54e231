"""Zero-downtime model lifecycle: checkpoint atomicity, swap-aware caches,
canary/rollback hot-swap across every serving surface, chaos fault kinds,
the engine admin endpoint, and the swap soak's fast mode.

Covers PR 10: `tpu/swap.py` ModelSwapManager + the crash-atomic
`tpu/checkpoint.py`, the ResponseCache model-version epoch, the
`swap_corrupt`/`swap_crash` chaos kinds, `POST /admin/swap`, and checkpoint
round-trips under the hard param layouts (int8-quantized, mesh-sharded).
"""

import asyncio
import json
import os
import time

import numpy as np
import pytest

from arkflow_tpu.batch import MessageBatch
from arkflow_tpu.components import Resource, ensure_plugins_loaded
from arkflow_tpu.components.registry import build_component
from arkflow_tpu.errors import ConfigError, SwapError

ensure_plugins_loaded()

TINY_BERT = {"vocab_size": 512, "hidden": 32, "layers": 2, "heads": 4,
             "ffn": 64, "max_positions": 64, "num_labels": 2}
TINY_LM = {"vocab_size": 128, "dim": 16, "layers": 1, "heads": 2,
           "kv_heads": 2, "ffn": 32, "max_seq": 64}


def _bert_proc(tmp_path=None, **overrides):
    cfg = {
        "type": "tpu_inference", "model": "bert_classifier",
        "model_config": TINY_BERT, "max_seq": 16,
        "batch_buckets": [2, 4], "seq_buckets": [16],
    }
    cfg.update(overrides)
    return build_component("processor", cfg, Resource())


def _leaf(params):
    """One concrete float leaf for identity checks."""
    import jax

    leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(params)
              if hasattr(x, "dtype")
              and np.issubdtype(np.asarray(x).dtype, np.floating)]
    return leaves[0]


# -- checkpoint: crash-atomic save + clean restore errors --------------------


def test_checkpoint_save_is_atomic_and_replaces(tmp_path):
    import jax

    from arkflow_tpu.tpu import checkpoint

    p = str(tmp_path / "ck")
    a = {"w": np.arange(6, dtype=np.float32).reshape(2, 3)}
    checkpoint.save(p, a)
    b = {"w": np.full((2, 3), 7.0, np.float32)}
    checkpoint.save(p, b)  # replace an existing checkpoint in place
    out = checkpoint.restore(p, jax.tree_util.tree_map(np.zeros_like, b))
    assert np.array_equal(np.asarray(out["w"]), b["w"])
    # no temp/old siblings survive a completed save — except the digest
    # manifest, the one INTENTIONAL sibling (tpu/integrity.py: restore
    # verifies the tree against it)
    leftovers = [f for f in os.listdir(tmp_path)
                 if f not in ("ck", "ck.digests.json")]
    assert leftovers == []
    assert (tmp_path / "ck.digests.json").exists()


def test_checkpoint_leftover_tmp_from_crashed_save_is_harmless(tmp_path):
    from arkflow_tpu.tpu import checkpoint

    p = tmp_path / "ck"
    # emulate a crash mid-save: a stale half-written temp sibling on disk —
    # from ANOTHER (dead) process, which is the realistic case: a crashed
    # saver never cleans its own siblings, so a same-pid-only cleanup would
    # leak full-size checkpoint copies forever
    stale_other = tmp_path / ".ck.tmp-99999999"
    stale_other.mkdir()
    (stale_other / "garbage").write_bytes(b"\x00\x01partial")
    stale_old = tmp_path / ".ck.old-99999999"
    stale_old.mkdir()
    stale = tmp_path / f".ck.tmp-{os.getpid()}"
    stale.mkdir()
    (stale / "garbage").write_bytes(b"\x00\x01partial")
    params = {"w": np.ones(4, np.float32)}
    checkpoint.save(str(p), params)  # must clear the stale tmp and succeed
    out = checkpoint.restore(str(p), {"w": np.zeros(4, np.float32)})
    assert np.array_equal(np.asarray(out["w"]), params["w"])
    assert not stale.exists()
    assert not stale_other.exists() and not stale_old.exists()
    # restore never reads a temp sibling: only the committed path resolves
    with pytest.raises(ConfigError):
        checkpoint.restore(str(tmp_path / "other"), params)


def test_checkpoint_restore_mismatch_names_offending_leaf(tmp_path):
    from arkflow_tpu.tpu import checkpoint

    p = str(tmp_path / "ck")
    checkpoint.save(p, {"layer": {"w": np.ones((2, 2), np.float32)}})
    like = {"layer": {"w_other": np.zeros((2, 2), np.float32)}}
    with pytest.raises(ConfigError) as ei:
        checkpoint.restore(p, like)
    msg = str(ei.value)
    # the error names the offending leaves, not an orbax traceback
    assert "w_other" in msg or "['layer']" in msg
    assert "failed to restore" in msg


def test_checkpoint_restore_truncated_file_raises_config_error(tmp_path):
    from arkflow_tpu.tpu import checkpoint

    p = tmp_path / "ck"
    params = {"w": np.arange(1024, dtype=np.float32)}
    checkpoint.save(str(p), params)
    # mangle every data file in the checkpoint tree (zarr chunk payloads)
    mangled = 0
    for root, _dirs, files in os.walk(p):
        for f in files:
            fp = os.path.join(root, f)
            if os.path.getsize(fp) > 8:
                with open(fp, "r+b") as fh:
                    fh.truncate(4)
                mangled += 1
    assert mangled > 0
    with pytest.raises(ConfigError):
        checkpoint.restore(str(p), {"w": np.zeros(1024, np.float32)})


# -- checkpoint round-trips under the hard param layouts ---------------------


def test_checkpoint_roundtrip_int8_quantized_params(tmp_path):
    """Save the W8A8 serving tree (int8 + f32 scales + bf16 rest), restore
    into a like-structured tree: bitwise equivalence on every leaf."""
    import jax

    from arkflow_tpu.models import get_model
    from arkflow_tpu.models.quantize import quantize_for_serving
    from arkflow_tpu.tpu import checkpoint

    fam = get_model("bert_classifier")
    cfg = fam.make_config(**TINY_BERT)
    qparams, n_q = quantize_for_serving(fam.init(jax.random.PRNGKey(0), cfg))
    assert n_q > 0
    p = str(tmp_path / "ck_int8")
    checkpoint.save(p, qparams)
    like = jax.tree_util.tree_map(lambda a: np.zeros_like(np.asarray(a)), qparams)
    out = checkpoint.restore(p, like)
    flat_in = jax.tree_util.tree_leaves(qparams)
    flat_out = jax.tree_util.tree_leaves(out)
    assert len(flat_in) == len(flat_out)
    for a, b in zip(flat_in, flat_out):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype
        assert np.array_equal(a, b)


def test_checkpoint_roundtrip_between_sharded_and_host_layouts(tmp_path):
    """Save mesh-sharded (tp) params, restore into the host layout — and the
    reverse: save host, restore into the sharded layout. Bitwise parity both
    ways; the sharded restore keeps its shardings."""
    import jax

    from arkflow_tpu.models import get_model
    from arkflow_tpu.parallel.mesh import MeshSpec, create_mesh, shard_params
    from arkflow_tpu.tpu import checkpoint

    fam = get_model("bert_classifier")
    cfg = fam.make_config(**TINY_BERT)
    host = fam.init(jax.random.PRNGKey(3), cfg)
    mesh = create_mesh(MeshSpec(tp=2), devices=jax.devices()[:2])
    axes = {name: name for name in mesh.axis_names}
    sharded = shard_params(host, fam.param_specs(cfg, axes), mesh)

    # sharded -> save -> restore into host layout
    p1 = str(tmp_path / "ck_sharded")
    checkpoint.save(p1, sharded)
    like_host = jax.tree_util.tree_map(
        lambda a: np.zeros_like(np.asarray(a)), host)
    back_host = checkpoint.restore(p1, like_host)
    for a, b in zip(jax.tree_util.tree_leaves(host),
                    jax.tree_util.tree_leaves(back_host)):
        assert np.array_equal(np.asarray(a), np.asarray(b))

    # host -> save -> restore into the sharded layout
    p2 = str(tmp_path / "ck_host")
    checkpoint.save(p2, host)
    back_sharded = checkpoint.restore(p2, sharded)
    for a, b in zip(jax.tree_util.tree_leaves(sharded),
                    jax.tree_util.tree_leaves(back_sharded)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the restored tree still carries device shardings (not host numpy)
    lead = jax.tree_util.tree_leaves(back_sharded)[0]
    assert getattr(lead, "sharding", None) is not None


# -- response cache: model-version epoch -------------------------------------


def test_respcache_epoch_post_swap_duplicate_misses():
    from arkflow_tpu.runtime.respcache import ResponseCache

    cache = ResponseCache(capacity=8, ttl_s=None, name="epoch-test")
    calls = []

    async def compute():
        calls.append(1)
        return {"x": np.arange(3)}

    async def go():
        k = b"fingerprint-1"
        await cache.get_or_compute(k, compute)
        await cache.get_or_compute(k, compute)  # pre-swap duplicate: hit
        assert len(calls) == 1
        cache.bump_epoch()
        assert cache.epoch == 1
        assert len(cache) == 0  # flushed
        # REGRESSION: the post-swap duplicate must MISS — the same
        # fingerprint against new weights is a different answer
        await cache.get_or_compute(k, compute)
        assert len(calls) == 2
        await cache.get_or_compute(k, compute)  # and re-caches under epoch 1
        assert len(calls) == 2
        assert cache.report()["epoch"] == 1

    asyncio.run(go())


# -- swap config validation ---------------------------------------------------


def test_parse_swap_config_validation():
    from arkflow_tpu.tpu.swap import SwapConfig, parse_swap_config

    assert parse_swap_config(None) == SwapConfig()
    cfg = parse_swap_config({"canary": {"rows": 2, "min_agreement": 0.5},
                             "drain_timeout": "5s"})
    assert cfg.canary_rows == 2 and cfg.min_agreement == 0.5
    assert cfg.drain_timeout_s == 5.0
    for bad in (
        {"bogus": 1},
        {"canary": {"rows": -1}},
        {"canary": {"rows": True}},
        {"canary": {"min_agreement": 1.5}},
        {"canary": {"nope": 1}},
        {"drain_timeout": "0s"},
        "not-a-mapping",
    ):
        with pytest.raises(ConfigError):
            parse_swap_config(bad)


def test_stream_config_validates_swap_through_fault_wrapper():
    from arkflow_tpu.config import StreamConfig

    base = {
        "input": {"type": "memory", "messages": ["x"]},
        "output": {"type": "drop"},
        "pipeline": {"processors": [{
            "type": "fault",
            "inner": {"type": "tpu_inference", "model": "bert_classifier",
                      "swap": {"canary": {"rows": -3}}},
        }]},
    }
    with pytest.raises(ConfigError, match="canary.rows"):
        StreamConfig.from_mapping(base)
    # a well-formed swap block parses (no jax import, no model build)
    base["pipeline"]["processors"][0]["inner"]["swap"] = {
        "canary": {"rows": 4}, "drain_timeout": "10s"}
    StreamConfig.from_mapping(base)


def test_fault_schedule_swap_kinds_processor_only():
    from arkflow_tpu.plugins.fault.schedule import parse_faults
    from arkflow_tpu.plugins.fault.wrappers import INPUT_KINDS, PROCESSOR_KINDS

    specs = parse_faults([{"kind": "swap_corrupt", "at": 1},
                          {"kind": "swap_crash", "at": 2}],
                         PROCESSOR_KINDS, "processor")
    assert [s.kind for s in specs] == ["swap_corrupt", "swap_crash"]
    with pytest.raises(ConfigError):
        parse_faults([{"kind": "swap_corrupt", "at": 1}], INPUT_KINDS, "input")


# -- the swap manager across serving surfaces --------------------------------


def test_runner_hot_swap_identical_weights_keeps_outputs(tmp_path):
    from arkflow_tpu.tpu import checkpoint

    proc = _bert_proc(response_cache={"capacity": 8, "ttl": "60s"})
    ck = str(tmp_path / "ck")
    checkpoint.save(ck, proc.runner.params)
    batch = MessageBatch.new_binary([b"alpha", b"beta"])

    async def go():
        before = await proc.process(batch)
        rep = await proc.swapper.swap(ck)
        assert rep["version"] == 1 and rep["completed"] == 1
        assert proc.swapper.report()["state"] == "idle"
        # swap-aware cache: committed swap bumped the epoch
        assert proc.cache.epoch == 1
        after = await proc.process(batch)
        assert before[0] == after[0]

    asyncio.run(go())


def test_pool_rolling_swap_flips_every_member(tmp_path):
    import jax

    from arkflow_tpu.tpu import checkpoint

    proc = _bert_proc(device_pool=2)
    pool = proc.runner
    ck = str(tmp_path / "ck")
    checkpoint.save(ck, pool.members[0].params)
    before = [_leaf(m.params).copy() for m in pool.members]

    async def go():
        rep = await proc.swapper.swap(ck)
        assert rep["version"] == 1 and rep["units"] == 2
        for m, old in zip(pool.members, before):
            new = _leaf(m.params)
            # identical weights restored: values equal, but the tree was
            # actually REPLACED (fresh device buffers, not the old objects)
            assert np.array_equal(new, old)
        # the pool still serves
        out = await proc.process(MessageBatch.new_binary([b"post-swap row"]))
        assert out[0].num_rows == 1

    asyncio.run(go())


def test_swap_corrupt_checkpoint_rolls_back_with_old_weights_serving(tmp_path):
    from arkflow_tpu.tpu import checkpoint

    proc = _bert_proc(device_pool=2)
    pool = proc.runner
    ck = str(tmp_path / "ck")
    checkpoint.save(ck, pool.members[0].params)
    batch = MessageBatch.new_binary([b"steady row 1", b"steady row 2"])

    async def go():
        before = await proc.process(batch)
        proc.swapper.inject_swap_fault("swap_corrupt")
        with pytest.raises(SwapError, match="rolled back"):
            await proc.swapper.swap(ck)
        rep = proc.swapper.report()
        assert rep["version"] == 0 and rep["rolled_back"] == 1
        after = await proc.process(batch)
        assert before[0] == after[0]  # old version serving throughout

    asyncio.run(go())


def test_swap_crash_mid_roll_rolls_back_flipped_members(tmp_path):
    from arkflow_tpu.tpu import checkpoint

    proc = _bert_proc(device_pool=2)
    pool = proc.runner
    ck = str(tmp_path / "ck")
    checkpoint.save(ck, pool.members[0].params)
    originals = [m.params for m in pool.members]

    async def go():
        proc.swapper.inject_swap_fault("swap_crash")
        with pytest.raises(SwapError, match="mid-swap"):
            await proc.swapper.swap(ck)
        # the partially-rolled flip was undone: every member is back on the
        # EXACT pre-swap tree (same objects, not just equal values)
        for m, orig in zip(pool.members, originals):
            assert m.params is orig
        rep = proc.swapper.report()
        assert rep["version"] == 0 and rep["rolled_back"] == 1

    asyncio.run(go())


def test_rollback_after_partial_flip_flushes_cache_epoch(tmp_path):
    """A flipped member may have answered live requests with the candidate
    weights before the roll failed: the flush hooks must run on a
    partial-flip rollback too, so no cache can serve the rolled-back
    candidate's responses (canary-stage rejections flip nothing and flush
    nothing — the old weights' entries are still correct)."""
    from arkflow_tpu.tpu import checkpoint

    proc = _bert_proc(device_pool=2,
                      response_cache={"capacity": 8, "ttl": "60s"})
    ck = str(tmp_path / "ck")
    checkpoint.save(ck, proc.runner.members[0].params)

    async def go():
        # canary rejection: nothing flipped, epoch untouched
        proc.swapper.inject_swap_fault("swap_corrupt")
        with pytest.raises(SwapError):
            await proc.swapper.swap(ck)
        assert proc.cache.epoch == 0
        # crash after the first member flipped: rollback AND flush
        proc.swapper.inject_swap_fault("swap_crash")
        with pytest.raises(SwapError):
            await proc.swapper.swap(ck)
        assert proc.cache.epoch == 1

    asyncio.run(go())


def test_continuous_swap_keeps_processor_params_alias_in_sync(tmp_path):
    """The continuous unit must update TpuGenerateProcessor.params on every
    flip, or the boot-time tree stays pinned in device memory forever and
    introspection reads version-0 weights after N swaps."""
    from arkflow_tpu.tpu import checkpoint

    proc = build_component("processor", {
        "type": "tpu_generate", "model": "decoder_lm", "model_config": TINY_LM,
        "max_input": 16, "max_new_tokens": 2, "batch_buckets": [2],
        "seq_buckets": [16], "serving": "continuous", "slots": 2,
        "page_size": 4,
    }, Resource())
    ck = str(tmp_path / "ck")
    checkpoint.save(ck, proc.host_params)
    boot_params = proc.params

    async def go():
        await proc.swapper.swap(ck)
        assert proc.params is proc._server.params
        assert proc.params is not boot_params

    asyncio.run(go())


def test_swap_already_in_progress_rejected():
    from arkflow_tpu.tpu.swap import ModelSwapManager, SwapConfig

    class _Unit:
        label = "u"

        def __init__(self):
            self.params = {"w": np.zeros(2)}

        def live(self):
            return self.params

        def place(self, host):
            return host

        async def adopt(self, placed):
            old, self.params = self.params, placed
            return old

        async def probe(self):
            return None

    started = asyncio.Event()

    def slow_prepare(path):
        time.sleep(0.3)
        return {"w": np.ones(2)}

    mgr = ModelSwapManager(
        name="dummy", config=SwapConfig(canary_rows=0),
        prepare=slow_prepare, canary=lambda p: np.zeros(1), units=[_Unit()])

    async def go():
        async def first():
            started.set()
            return await mgr.swap("/a")

        t = asyncio.create_task(first())
        await started.wait()
        await asyncio.sleep(0.05)  # let first() enter the lock
        with pytest.raises(SwapError, match="in progress"):
            await mgr.swap("/b")
        rep = await t
        assert rep["version"] == 1

    asyncio.run(go())


def test_generate_batch_swap_keeps_outputs(tmp_path):
    from arkflow_tpu.tpu import checkpoint

    proc = build_component("processor", {
        "type": "tpu_generate", "model": "decoder_lm", "model_config": TINY_LM,
        "max_input": 16, "max_new_tokens": 4, "batch_buckets": [2],
        "seq_buckets": [16],
    }, Resource())
    ck = str(tmp_path / "ck")
    checkpoint.save(ck, proc.host_params)
    batch = MessageBatch.new_binary([b"one small step", b"for a model"])

    async def go():
        before = await proc.process(batch)
        rep = await proc.swapper.swap(ck)
        assert rep["version"] == 1
        after = await proc.process(batch)
        assert before[0] == after[0]

    asyncio.run(go())


def test_generate_continuous_swap_drains_and_resets_caches(tmp_path):
    from arkflow_tpu.tpu import checkpoint

    proc = build_component("processor", {
        "type": "tpu_generate", "model": "decoder_lm", "model_config": TINY_LM,
        "max_input": 16, "max_new_tokens": 4, "batch_buckets": [2],
        "seq_buckets": [16], "serving": "continuous", "slots": 2,
        "page_size": 4, "prefix_cache_pages": 8,
    }, Resource())
    srv = proc._server
    ck = str(tmp_path / "ck")
    checkpoint.save(ck, proc.host_params)
    batch = MessageBatch.new_binary([b"repeated prompt text goes here"])

    async def go():
        before = await proc.process(batch)
        await proc.process(batch)  # finished prompt donates prefix pages
        assert len(srv._prefix_cache) > 0
        rep = await proc.swapper.swap(ck)
        assert rep["version"] == 1
        # stale KV against new weights would be silent corruption: the swap
        # reset the page pools and flushed the prefix cache
        assert len(srv._prefix_cache) == 0
        assert len(srv._free_pages) == srv.num_pages - 1
        assert not srv._draining
        after = await proc.process(batch)
        assert before[0] == after[0]  # identical weights => identical text

    asyncio.run(go())


def test_generate_continuous_swap_under_inflight_load(tmp_path):
    """Requests racing a swap are never dropped: those admitted before the
    drain finish on the old weights; those queued during it serve after the
    flip. Identical weights => every output matches the no-swap run."""
    from arkflow_tpu.tpu import checkpoint

    proc = build_component("processor", {
        "type": "tpu_generate", "model": "decoder_lm", "model_config": TINY_LM,
        "max_input": 16, "max_new_tokens": 6, "batch_buckets": [2],
        "seq_buckets": [16], "serving": "continuous", "slots": 2,
        "page_size": 4,
    }, Resource())
    ck = str(tmp_path / "ck")
    checkpoint.save(ck, proc.host_params)
    prompts = [f"prompt number {i} padding words".encode() for i in range(6)]

    async def go():
        baseline = await proc.process(MessageBatch.new_binary(prompts))
        tasks = [asyncio.create_task(
            proc.process(MessageBatch.new_binary([p]))) for p in prompts]
        await asyncio.sleep(0.01)  # let some admissions land
        rep = await proc.swapper.swap(ck)
        assert rep["version"] == 1
        outs = await asyncio.gather(*tasks)
        got = {bytes(o[0].to_binary()[0]): o[0].column("generated")[0].as_py()
               for o in outs}
        want = {bytes(p): g.as_py() for p, g in zip(
            baseline[0].to_binary(), baseline[0].column("generated"))}
        assert got == want

    asyncio.run(go())


# -- engine admin endpoint ----------------------------------------------------


def test_engine_admin_swap_endpoint_and_health(tmp_path):
    import aiohttp
    import jax

    from arkflow_tpu.config import EngineConfig
    from arkflow_tpu.models import get_model
    from arkflow_tpu.runtime.engine import Engine
    from arkflow_tpu.tpu import checkpoint

    # the engine builds its runner from (family, config, seed=0): the same
    # deterministic init here yields byte-identical candidate weights
    fam = get_model("bert_classifier")
    cfg_model = fam.make_config(**TINY_BERT)
    with jax.default_device(jax.devices("cpu")[0]):
        host = fam.init(jax.random.PRNGKey(0), cfg_model)
    ck = str(tmp_path / "ck")
    checkpoint.save(ck, host)

    port = 18111
    cfg = EngineConfig.from_mapping({
        "streams": [{
            "name": "swap-stream",
            "input": {"type": "generate", "payload": "swap live row",
                      "interval": "20ms", "batch_size": 2},
            "pipeline": {"thread_num": 1, "processors": [{
                "type": "tpu_inference", "model": "bert_classifier",
                "model_config": TINY_BERT, "max_seq": 16,
                "batch_buckets": [2], "seq_buckets": [16],
            }]},
            "output": {"type": "drop"},
        }],
        "health_check": {"enabled": True, "host": "127.0.0.1", "port": port},
    })
    engine = Engine(cfg)

    async def go():
        run_task = asyncio.create_task(engine.run())
        base = f"http://127.0.0.1:{port}"
        try:
            async with aiohttp.ClientSession() as s:
                deadline = time.monotonic() + 30
                up = False
                while time.monotonic() < deadline and not up:
                    await asyncio.sleep(0.1)
                    try:
                        async with s.get(base + "/health") as r:
                            up = r.status == 200
                    except aiohttp.ClientError:
                        continue
                assert up, "health server never came up"
                # bad body -> 400
                async with s.post(base + "/admin/swap", data=b"}{") as r:
                    assert r.status == 400
                async with s.post(base + "/admin/swap", json={}) as r:
                    assert r.status == 400
                # unknown stream -> 404
                async with s.post(base + "/admin/swap",
                                  json={"checkpoint": ck,
                                        "stream": "nope"}) as r:
                    assert r.status == 404
                # the real swap -> 200, committed
                async with s.post(base + "/admin/swap",
                                  json={"checkpoint": ck}) as r:
                    body = json.loads(await r.text())
                    assert r.status == 200, body
                assert body["ok"] is True
                rep = body["results"]["swap-stream"][0]
                assert rep["version"] == 1 and rep["ok"] is True
                # a missing checkpoint -> rejected, rolled back, 409
                async with s.post(base + "/admin/swap",
                                  json={"checkpoint": str(tmp_path / "no")}) as r:
                    body = json.loads(await r.text())
                    assert r.status == 409
                assert body["ok"] is False
                assert "rolled back" in body["results"]["swap-stream"][0]["error"]
                # /health carries swap/version state
                async with s.get(base + "/health") as r:
                    health = json.loads(await r.text())
                sw = health["stream_health"]["swap-stream"]["swap"][0]
                assert sw["version"] == 1
                assert sw["completed"] == 1 and sw["rolled_back"] == 1
        finally:
            engine.shutdown()
            try:
                await asyncio.wait_for(run_task, timeout=15)
            except (asyncio.TimeoutError, Exception):
                run_task.cancel()

    asyncio.run(go())


# -- soak acceptance ----------------------------------------------------------


def test_swap_soak_fast_mode_smoke():
    """Acceptance gate (tools/chaos_soak.py --swap --fast): under sustained
    offered load, a corrupt candidate rolls back with the old version
    serving throughout, then a rolling hot-swap commits across a
    device_pool and a continuous tpu_generate server with zero failed/lost
    requests and delivered p99 within the SLO."""
    import importlib
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    try:
        chaos_soak = importlib.import_module("chaos_soak")
    finally:
        sys.path.pop(0)
    verdict = chaos_soak.run_swap_soak(seconds=90.0, seed=7, fast=True)
    assert verdict["pass"], verdict
    pool = verdict["pool"]
    assert pool["corrupt_rolled_back"] and pool["good_committed"]
    assert pool["lost_rows"] == 0 and pool["failed_rows"] == 0
    assert pool["swap"]["version"] == 1 and pool["swap"]["rolled_back"] == 1
    assert pool["cache_epoch"] == 1
    gen = verdict["generate"]
    assert gen["good_committed"] and gen["lost_rows"] == 0
    assert gen["e2e_p99_ms"] <= gen["slo_ms"]
