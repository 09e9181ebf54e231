"""Silent-data-corruption defense plane (tpu/integrity.py): param-tree
digests, tie-free golden references (deterministic across restarts, per
serving dtype, on every registered family), the CORRUPT quarantine state,
monitor quarantine-and-repair on a live device pool, hot-swap coexistence,
checkpoint digest manifests, response-cache epoch flush on quarantine,
cluster-tier fencing + shadow-verify config, engine surfaces, and the
--sdc soak's fast tier-1 smoke."""

from __future__ import annotations

import asyncio
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from arkflow_tpu.components import ensure_plugins_loaded
from arkflow_tpu.components.base import Resource
from arkflow_tpu.components.registry import build_component
from arkflow_tpu.errors import ConfigError
from arkflow_tpu.tpu.integrity import (
    MARGIN_FLOOR,
    IntegrityConfig,
    combined_digest,
    diff_digests,
    find_golden_reference,
    parse_integrity_config,
    tree_digests,
)

ensure_plugins_loaded()

TINY_BERT = {"vocab_size": 512, "hidden": 32, "layers": 2, "heads": 4,
             "ffn": 64, "max_positions": 64, "num_labels": 2}

#: one tiny config per registered family (the tie-free search must succeed
#: for EVERY family anyone can point the integrity block at)
FAMILY_CONFIGS = {
    "bert_classifier": TINY_BERT,
    "decoder_lm": {"vocab_size": 128, "dim": 16, "layers": 1, "heads": 2,
                   "kv_heads": 2, "ffn": 32, "max_seq": 64},
    "lstm_ae": {"features": 4, "hidden": 16, "latent": 8, "window": 10},
    "vit_embedder": {"image_size": 32, "patch": 16, "hidden": 32,
                     "layers": 2, "heads": 4, "ffn": 64},
}


def _integrity_proc(**extra):
    """A tpu_inference processor with the integrity monitor attached and a
    probe cadence the test drives by hand (999s background interval)."""
    cfg = {
        "type": "tpu_inference", "model": "bert_classifier",
        "model_config": TINY_BERT, "max_seq": 16,
        "batch_buckets": [2], "seq_buckets": [16], "warmup": True,
        "integrity": {"probe_interval": "999s", "digest_every": 1},
    }
    cfg.update(extra)
    return build_component("processor", cfg, Resource())


# -- config parse ------------------------------------------------------------


def test_parse_integrity_config():
    assert parse_integrity_config(None) is None
    out = parse_integrity_config({"probe_interval": "500ms",
                                  "digest_every": 2,
                                  "golden": {"rows": 4, "seq": 8, "seed": 9},
                                  "repair": False})
    assert out.probe_interval_s == 0.5
    assert out.digest_every == 2
    assert (out.golden_rows, out.golden_seq, out.golden_seed) == (4, 8, 9)
    assert out.repair is False
    # defaults survive a partial block
    d = parse_integrity_config({})
    assert d.probe_interval_s == 10.0 and d.digest_every == 3 and d.repair

    with pytest.raises(ConfigError, match="unknown keys"):
        parse_integrity_config({"cadence": "1s"})
    with pytest.raises(ConfigError, match="must be a mapping"):
        parse_integrity_config("1s")
    with pytest.raises(ConfigError, match="must be positive"):
        parse_integrity_config({"probe_interval": "0s"})
    with pytest.raises(ConfigError, match="digest_every"):
        parse_integrity_config({"digest_every": -1})
    with pytest.raises(ConfigError, match="golden"):
        parse_integrity_config({"golden": {"rows": 0}})
    with pytest.raises(ConfigError, match="repair"):
        parse_integrity_config({"repair": "yes"})


def test_engine_config_validates_integrity_block():
    """--validate catches a bad integrity block at parse time, through
    fault-wrapper nesting, without building a stream."""
    from arkflow_tpu.config import StreamConfig

    def stream(integrity):
        return {
            "name": "s",
            "input": {"type": "memory", "messages": ["x"]},
            "pipeline": {"thread_num": 1, "processors": [
                {"type": "fault", "inner": {
                    "type": "tpu_inference", "model": "bert_classifier",
                    "model_config": TINY_BERT, "max_seq": 16,
                    "integrity": integrity},
                 "faults": [{"kind": "bitflip", "at": 3}]}]},
            "output": {"type": "drop"},
        }

    StreamConfig.from_mapping(stream({"probe_interval": "1s"}))
    with pytest.raises(ConfigError, match="unknown keys"):
        StreamConfig.from_mapping(stream({"bogus": 1}))


# -- param-tree digests ------------------------------------------------------


def test_tree_digests_detect_value_dtype_shape_and_missing_leaves():
    tree = {"w": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": np.zeros(3, np.float32)}
    base = tree_digests(tree)
    assert set(base) == {"['w']", "['b']"}
    assert diff_digests(base, tree_digests(tree)) == []

    flipped = {**tree, "w": tree["w"].copy()}
    flipped["w"][1, 2] += 1e-3
    assert diff_digests(base, tree_digests(flipped)) == ["['w']"]
    recast = {**tree, "b": tree["b"].astype(np.float16)}
    assert diff_digests(base, tree_digests(recast)) == ["['b']"]
    reshaped = {**tree, "w": tree["w"].reshape(3, 2)}
    assert diff_digests(base, tree_digests(reshaped)) == ["['w']"]
    assert diff_digests(base, tree_digests({"w": tree["w"]})) == ["['b']"]


def test_combined_digest_is_order_independent_and_content_sensitive():
    a = {"x": "aa", "y": "bb"}
    assert combined_digest(a) == combined_digest({"y": "bb", "x": "aa"})
    assert combined_digest(a) != combined_digest({"x": "aa", "y": "cc"})


# -- CORRUPT state machine ---------------------------------------------------


def test_corrupt_state_is_dead_adjacent_until_explicit_repair():
    from arkflow_tpu.tpu.health import CORRUPT, DEAD, HEALTHY, RunnerHealth

    h = RunnerHealth(name="m0")
    h.mark_corrupt("golden probe failed")
    assert h.state == CORRUPT
    assert not h.available(0.0)
    assert not h.try_begin_probe()
    assert not h.join_or_begin_probe()
    # neither step successes nor incidents move a quarantined member: a
    # corrupt chip completes steps fine — that is the failure mode
    h.mark_success()
    assert h.state == CORRUPT
    h.mark_unhealthy("deadline miss")
    assert h.state == CORRUPT
    # only the verified repair path re-admits
    assert h.mark_repaired()
    assert h.state == HEALTHY and h.available(0.0)
    # repaired from any other state is a no-op
    assert not h.mark_repaired()

    dead = RunnerHealth(name="m1")
    dead._set(DEAD)
    dead.mark_corrupt("late report")
    assert dead.state == DEAD  # terminal outranks quarantine
    assert not dead.mark_repaired()  # repair never resurrects DEAD


# -- tie-free golden references ----------------------------------------------


def _family_and_params(name, seed=0):
    from arkflow_tpu.models.registry import get_model
    from arkflow_tpu.tpu.runner import init_host_params

    fam = get_model(name)
    cfg = fam.make_config(**FAMILY_CONFIGS[name])
    return fam, cfg, init_host_params(fam, cfg, seed)


def test_golden_reference_restart_stable():
    """Same (family, cfg, seed) => bitwise-identical batch + signature, so
    a process restart (or a peer worker) reproduces the same reference."""
    fam, cfg, params = _family_and_params("bert_classifier")
    a = find_golden_reference(fam, cfg, params, rows=2, seq=16,
                              seed=0x90D, serving_dtype="bfloat16")
    b = find_golden_reference(fam, cfg, params, rows=2, seq=16,
                              seed=0x90D, serving_dtype="bfloat16")
    assert a.seed == b.seed
    assert sorted(a.inputs) == sorted(b.inputs)
    for k in a.inputs:
        np.testing.assert_array_equal(a.inputs[k], b.inputs[k])
    np.testing.assert_array_equal(a.signature, b.signature)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_golden_margin_clears_dtype_noise_floor(dtype):
    """The seed search must land a batch whose top-1/top-2 gap clears the
    serving dtype's rounding noise — otherwise benign drift between the
    host reference and the device step would read as corruption."""
    fam, cfg, params = _family_and_params("bert_classifier")
    ref = find_golden_reference(fam, cfg, params, rows=2, seq=16,
                                seed=0x90D, serving_dtype=dtype)
    assert ref.margin >= MARGIN_FLOOR[dtype]
    assert ref.signature.shape == (2,)


@pytest.mark.parametrize("name", sorted(FAMILY_CONFIGS))
def test_golden_reference_tie_free_for_every_family(name):
    fam, cfg, params = _family_and_params(name)
    ref = find_golden_reference(fam, cfg, params, rows=2, seq=8,
                                seed=0x90D, serving_dtype=None)
    assert ref.margin >= MARGIN_FLOOR[None]
    # the reference answer really is the host forward's argmax signature
    from arkflow_tpu.tpu.swap import argmax_signature

    out = fam.apply(params, cfg, **ref.inputs)
    np.testing.assert_array_equal(
        ref.signature,
        argmax_signature({k: np.asarray(v) for k, v in out.items()}))


# -- monitor: quarantine and repair on a live pool ---------------------------


def test_monitor_detects_bitflip_quarantines_and_repairs():
    """E2E on a 2-member device pool: a flipped param leaf is caught by the
    digest pass, proven by the golden probe, quarantined (CORRUPT), hooks
    fire (cache epoch), repair re-adopts the retained host tree, and the
    member is re-admitted — all inside the monitor's own probe ticks."""
    proc = _integrity_proc(device_pool=2)
    mon = proc.integrity
    assert mon is not None and len(mon.members) == 2

    async def go():
        rep = await mon.probe_now()
        assert rep["checked"] == 2 and rep["ok"] == 2, rep
        assert mon.digest_epoch() is not None  # every member baselined
        epoch0 = mon.digest_epoch()

        hook_fires = []
        mon.add_quarantine_hook(lambda: hook_fires.append(1))
        proc.runner.members[1].inject_step_fault("bitflip")
        rep = await mon.probe_now()
        assert rep["mismatches"] == 1, rep
        assert rep["repaired"] == 1, rep
        assert hook_fires, "quarantine hooks must fire on proven corruption"
        assert mon.n_quarantined == 1 and mon.n_repaired == 1
        # repaired back to the SAME retained host tree => same epoch
        assert mon.digest_epoch() == epoch0
        states = [m.state() for m in mon.members]
        assert states == ["healthy", "healthy"], states
        rep = await mon.probe_now()
        assert rep["ok"] == 2 and rep["mismatches"] == 0, rep

    asyncio.run(asyncio.wait_for(go(), timeout=300))


def test_monitor_repair_false_leaves_member_quarantined():
    proc = _integrity_proc(device_pool=2,
                           integrity={"probe_interval": "999s",
                                      "digest_every": 1, "repair": False})
    mon = proc.integrity

    async def go():
        await mon.probe_now()  # baseline
        proc.runner.members[0].inject_step_fault("bitflip")
        rep = await mon.probe_now()
        assert rep["mismatches"] == 1 and rep["repaired"] == 0, rep
        assert mon.members[0].state() == "corrupt"
        # subsequent ticks never resurrect it without the repair path
        rep = await mon.probe_now()
        assert rep["repaired"] == 0
        assert mon.members[0].state() == "corrupt"
        assert mon.report()["members"][0]["state"] == "corrupt"

    asyncio.run(asyncio.wait_for(go(), timeout=300))


def test_monitor_report_carries_member_state_and_probe_age():
    proc = _integrity_proc()
    mon = proc.integrity

    async def go():
        await mon.probe_now()
        rep = mon.report()
        assert rep["probes"] >= 1 and rep["mismatches"] == 0
        m0 = rep["members"][0]
        assert m0["state"] == "healthy"
        assert m0["last_probe"] == "ok"
        assert m0["last_probe_age_s"] >= 0.0
        assert "digest_epoch" in rep

    asyncio.run(asyncio.wait_for(go(), timeout=300))


# -- hot-swap coexistence ----------------------------------------------------


def test_swap_to_new_weights_never_false_quarantines_and_repair_keeps_them(
        tmp_path):
    """A committed swap to genuinely DIFFERENT weights must not read as
    corruption (the golden reference + digest baseline are rebuilt for the
    new version), and a post-swap repair converges to the NEW weights —
    never a silent rollback to the pre-swap tree."""
    import jax

    from arkflow_tpu.tpu import checkpoint
    from arkflow_tpu.tpu.runner import init_host_params

    proc = _integrity_proc(swap={"canary": {"min_agreement": 0.0}})
    mon = proc.integrity
    assert proc.swapper.integrity is mon, \
        "the builder must hand the monitor to the swap manager"

    async def go():
        rep = await mon.probe_now()
        assert rep["ok"] == 1 and rep["mismatches"] == 0, rep
        old_golden = mon.members[0].golden
        old_epoch = mon.digest_epoch()

        new_host = init_host_params(proc.runner.family, proc.runner.cfg, 42)
        ck = str(tmp_path / "ck42")
        checkpoint.save(ck, new_host)
        srep = await proc.swapper.swap(ck)
        assert srep["version"] == 1, srep

        assert not mon._suspended, "quiesce must end after the swap"
        assert mon.members[0].golden is not old_golden
        rep = await mon.probe_now()
        assert rep["mismatches"] == 0 and rep["ok"] == 1, \
            f"false quarantine after swap: {rep}"
        assert mon.digest_epoch() not in (None, old_epoch)

        proc.runner.inject_step_fault("bitflip")
        rep = await mon.probe_now()
        assert rep["mismatches"] == 1 and rep["repaired"] == 1, rep
        live = np.asarray(jax.tree_util.tree_leaves(proc.runner.params)[0])
        want = np.asarray(jax.tree_util.tree_leaves(new_host)[0])
        np.testing.assert_array_equal(live, want)  # no silent rollback

    asyncio.run(asyncio.wait_for(go(), timeout=300))


# -- checkpoint digest manifest ----------------------------------------------


def test_checkpoint_manifest_verifies_and_names_drifted_leaves(tmp_path):
    import json

    from arkflow_tpu.tpu import checkpoint

    tree = {"layer": {"w": np.arange(8, dtype=np.float32),
                      "b": np.ones(2, np.float32)}}
    ck = tmp_path / "ck"
    checkpoint.save(str(ck), tree)
    manifest = ck.parent / f"{ck.name}.digests.json"
    assert manifest.exists()

    like = {"layer": {"w": np.zeros(8, np.float32),
                      "b": np.zeros(2, np.float32)}}
    restored = checkpoint.restore(str(ck), like)
    np.testing.assert_array_equal(np.asarray(restored["layer"]["w"]),
                                  tree["layer"]["w"])

    # tamper: the manifest now describes different bytes for one leaf —
    # restore must fail loudly and NAME it
    doc = json.loads(manifest.read_text())
    leaf = next(k for k in doc["digests"] if "w" in k)
    doc["digests"][leaf] = "0" * 32
    manifest.write_text(json.dumps(doc))
    with pytest.raises(ConfigError, match="digest verification") as ei:
        checkpoint.restore(str(ck), like)
    assert "w" in str(ei.value)
    # verify=False and a missing manifest both restore unverified (the
    # crash window between tree flip and manifest write leaves exactly
    # a manifest-less tree behind)
    checkpoint.restore(str(ck), like, verify=False)
    manifest.unlink()
    checkpoint.restore(str(ck), like)


# -- response-cache epoch: post-quarantine duplicates recompute --------------


def test_quarantine_epoch_bump_makes_byte_identical_duplicate_recompute():
    from arkflow_tpu.runtime.respcache import ResponseCache

    cache = ResponseCache(capacity=8, name="itest")
    calls = []

    async def compute():
        calls.append(1)
        return f"answer-{len(calls)}"

    async def go():
        key = b"\x01" * 16  # one batch fingerprint, re-sent byte-identical
        a = await cache.get_or_compute(key, compute)
        b = await cache.get_or_compute(key, compute)
        assert a == b == "answer-1" and len(calls) == 1  # dedup works
        # integrity quarantine fires the epoch bump (the wiring under
        # test end-to-end in the --sdc soak): the SAME bytes must now
        # recompute — the cached answer may be poisoned
        cache.bump_epoch()
        c = await cache.get_or_compute(key, compute)
        assert c == "answer-2" and len(calls) == 2
        assert len(cache) == 1  # old-epoch entries were flushed, not kept

    asyncio.run(asyncio.wait_for(go(), timeout=30))


# -- cluster tier: config + fencing units ------------------------------------


def test_parse_remote_tpu_shadow_verify_validation():
    from arkflow_tpu.runtime.cluster import parse_remote_tpu_config

    base = {"workers": ["arkflow://h:1", "arkflow://h:2"]}
    assert parse_remote_tpu_config(base)["shadow_verify"] is None
    out = parse_remote_tpu_config({**base, "shadow_verify": {"fraction": 0.5}})
    assert out["shadow_verify"] == {"fraction": 0.5}
    assert parse_remote_tpu_config(
        {**base, "shadow_verify": {}})["shadow_verify"]["fraction"] == 0.05

    with pytest.raises(ConfigError, match="must be a mapping"):
        parse_remote_tpu_config({**base, "shadow_verify": 0.5})
    with pytest.raises(ConfigError, match="unknown keys"):
        parse_remote_tpu_config({**base, "shadow_verify": {"rate": 0.5}})
    for bad in (0, -0.1, 1.5, "lots"):
        with pytest.raises(ConfigError, match="fraction"):
            parse_remote_tpu_config({**base, "shadow_verify": {"fraction": bad}})


def test_dispatcher_shadow_cadence_is_deterministic():
    from arkflow_tpu.runtime.cluster import ClusterDispatcher

    urls = ["arkflow://h:1", "arkflow://h:2"]
    assert ClusterDispatcher(urls, shadow_verify={"fraction": 1.0}) \
        ._shadow_every == 1
    assert ClusterDispatcher(urls, shadow_verify={"fraction": 0.5}) \
        ._shadow_every == 2
    assert ClusterDispatcher(urls, shadow_verify={})._shadow_every == 20


def test_shadow_divergence_that_no_probe_explains_delivers_neither_answer():
    """Two batches diverge at once on one corrupt worker (two pipeline
    threads): the first tiebreak's probe makes the worker repair ON THE SPOT,
    so the second tiebreak's probes both come back clean — after the corrupt
    answer was given. One of the two answers is wrong and no probe says
    which: neither is delivered, the batch fails over to redelivery."""
    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.errors import ConnectError
    from arkflow_tpu.runtime.cluster import ClusterDispatcher

    disp = ClusterDispatcher(["arkflow://h:1", "arkflow://h:2"],
                             shadow_verify={"fraction": 1.0})
    primary, shadow = disp.workers.values()
    batch = MessageBatch.new_binary([b"row"])
    answers = {primary.url: [MessageBatch.new_binary([b"garbled"])],
               shadow.url: [MessageBatch.new_binary([b"clean"])]}

    async def attempt(w, b, **kw):
        return answers[w.url]

    async def clean_probe(w, req, timeout=None):
        return {"ok": True, "mismatches": 0, "corrupt": 0}

    disp._attempt, disp._unary = attempt, clean_probe
    with pytest.raises(ConnectError, match="neither answer is delivered"):
        asyncio.run(disp._attempt_shadow(primary, shadow, batch))
    assert disp.m_shadow["diverged"].value >= 1
    assert disp.m_integrity_fence.value == 0    # nobody was proven corrupt

    async def clean_side_unreachable(w, req, timeout=None):
        if w is shadow:
            raise ConnectError("probe timed out")
        return await clean_probe(w, req)

    disp._unary = clean_side_unreachable   # ... and the repaired one passes
    with pytest.raises(ConnectError, match="neither answer is delivered"):
        asyncio.run(disp._attempt_shadow(primary, shadow, batch))

    async def primary_fails_probe(w, req, timeout=None):
        return {"ok": True, "mismatches": int(w is primary), "corrupt": 0}

    disp._unary = primary_fails_probe
    out = asyncio.run(disp._attempt_shadow(primary, shadow, batch))
    assert out is answers[shadow.url] and disp.m_integrity_fence.value == 1


def test_dispatcher_fences_self_reported_corrupt_worker():
    """A heartbeat carrying integrity_corrupt > 0 fences that worker's
    incarnation immediately (no probe needed — the worker proved it
    itself) and fires the integrity hooks (ingest cache epoch bump)."""
    from arkflow_tpu.runtime.cluster import ClusterDispatcher

    d = ClusterDispatcher(["arkflow://h:1", "arkflow://h:2"],
                          name="fence-unit")
    hook_fires = []
    d.integrity_hooks.append(lambda: hook_fires.append(1))
    w = d.workers["arkflow://h:1"]
    w.alive = True
    w.incarnation = "inc-1"
    w.integrity_corrupt = 1

    asyncio.run(d._integrity_check(w))
    assert not w.alive
    assert w.is_fenced("inc-1")
    assert d.m_integrity_fence.value == 1
    assert hook_fires


def test_dispatcher_digest_outlier_needs_quorum_and_probe():
    """A digest-epoch outlier is NOT fenced below 3 reporting peers, and
    never without its own golden probe confirming (a clean probe means a
    different weights version mid-swap, not corruption)."""
    from arkflow_tpu.runtime.cluster import ClusterDispatcher

    urls = [f"arkflow://h:{i}" for i in (1, 2, 3)]
    d = ClusterDispatcher(urls, name="outlier-unit")
    for i, u in enumerate(urls):
        w = d.workers[u]
        w.alive = True
        w.incarnation = f"inc-{i}"
        w.param_digest = "aaaa"
    odd = d.workers[urls[0]]
    odd.param_digest = "bbbb"

    probed = []

    async def fake_unary(w, payload, timeout=None):
        probed.append(w.url)
        assert payload["action"] == "integrity_probe"
        return {"checked": 1, "ok": 1, "mismatches": 0, "corrupt": 0}

    d._unary = fake_unary
    # only 2 peers besides a missing digest: below quorum, no probe at all
    d.workers[urls[2]].param_digest = None
    asyncio.run(d._integrity_check(odd))
    assert probed == [] and odd.alive

    # full quorum, clean probe: admitted as a weights-version outlier and
    # the digest is remembered so every later beat doesn't re-probe
    d.workers[urls[2]].param_digest = "aaaa"
    asyncio.run(d._integrity_check(odd))
    assert probed == [odd.url]
    assert odd.alive and odd.digest_cleared == "bbbb"
    assert d.m_integrity_fence.value == 0
    asyncio.run(d._integrity_check(odd))
    assert probed == [odd.url]  # cleared: not probed again

    # a probe that CONFIRMS corruption fences through the incarnation path
    async def failing_unary(w, payload, timeout=None):
        return {"checked": 1, "ok": 0, "mismatches": 1, "corrupt": 1}

    d._unary = failing_unary
    odd.digest_cleared = None
    asyncio.run(d._integrity_check(odd))
    assert not odd.alive
    assert odd.is_fenced("inc-0")
    assert d.m_integrity_fence.value == 1


# -- engine surfaces ---------------------------------------------------------


def test_engine_health_reports_integrity_and_readiness_503_when_all_corrupt():
    """/health carries each processor's integrity report; /readiness treats
    an all-CORRUPT replica set exactly like all-DEAD — quarantined members
    complete steps, but their answers are proven wrong (503, not ready)."""
    import aiohttp

    from arkflow_tpu.config import EngineConfig
    from arkflow_tpu.runtime.engine import Engine

    cfg = EngineConfig.from_mapping({
        "streams": [{"name": "unused",
                     "input": {"type": "memory", "messages": []},
                     "pipeline": {"thread_num": 1, "processors": []},
                     "output": {"type": "drop"}}],
        "health_check": {"enabled": True, "host": "127.0.0.1", "port": 18123},
    })
    engine = Engine(cfg)
    engine._ready = True

    class FakeMonitor:
        def report(self):
            return {"probes": 4, "mismatches": 1, "quarantined": 1,
                    "repaired": 0,
                    "members": [{"state": "corrupt", "last_probe": "mismatch",
                                 "last_probe_age_s": 0.1}]}

    class FakeRunner:
        def health_report(self):
            return [{"state": "corrupt", "device": "0"},
                    {"state": "dead", "device": "1"}]

    class FakeProc:
        runner = FakeRunner()
        integrity = FakeMonitor()

    class FakePipeline:
        processors = [FakeProc()]

    class FakeStream:
        name = "corrupt-pool"
        pipeline = FakePipeline()

    engine.streams = [FakeStream()]
    health = engine.stream_health()
    assert health["corrupt-pool"]["integrity"][0]["quarantined"] == 1
    assert health["corrupt-pool"]["integrity"][0]["members"][0]["state"] \
        == "corrupt"

    async def go():
        await engine._start_health_server()
        try:
            async with aiohttp.ClientSession() as s:
                async with s.get("http://127.0.0.1:18123/readiness") as r:
                    assert r.status == 503
                    import json

                    body = json.loads(await r.text())
            assert body["dead_runner_streams"] == {"corrupt-pool": 2}
            assert body["runners"]["corrupt-pool"] == ["corrupt", "dead"]
        finally:
            await engine._runner.cleanup()

    asyncio.run(asyncio.wait_for(go(), timeout=15))


# -- acceptance: the SDC soak (fast tier-1 mode) -----------------------------


def test_chaos_soak_sdc_fast_mode_smoke():
    """Acceptance gate (tools/chaos_soak.py --sdc --fast): a bitflip on a
    live pool member is detected within a probe period, quarantined,
    repaired, and re-admitted with zero lost rows; a cluster worker armed
    with a persistent sdc fault is caught by shadow-verify's first
    divergent batch, fenced via the golden-probe tiebreak, its cached
    answers epoch-flushed — zero corrupted rows delivered, offered ==
    delivered + shed, and the repaired worker re-registers and serves."""
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    try:
        from chaos_soak import run_sdc_soak
    finally:
        sys.path.pop(0)

    verdict = run_sdc_soak(seconds=90.0, seed=7, fast=True)
    assert verdict["pass"], verdict
    assert verdict["pool"]["quarantined"] >= 1
    assert verdict["pool"]["repaired"] >= 1
    assert verdict["pool"]["detect_within_ok"]
    assert verdict["pool"]["delivered_rows"] == verdict["pool"]["offered_rows"]
    assert verdict["chaos"]["corrupted_delivered_rows"] == 0
    assert verdict["chaos"]["identity_ok"]
    assert verdict["chaos"]["shadow"]["diverged"] >= 1
    assert verdict["chaos"]["integrity_fences"] >= 1
    assert verdict["chaos"]["cache_epoch_bumps"] >= 1
    assert verdict["chaos"]["revived"]
