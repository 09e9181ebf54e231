"""Prefill/decode disaggregation (PR 18): kv_push wire codec, role-aware
routing + occupancy placement, page export/adopt parity on the generation
server (single-chip and tp=2 host mesh), cross-process bitwise adoption,
the retryable-refusal re-plan, per-role fleet scaling, and the TTFT
histogram. Codec/routing/fleet sections run without jax; the serving and
end-to-end cluster sections host real tiny continuous servers on CPU.
"""

from __future__ import annotations

import asyncio
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from arkflow_tpu.batch import MessageBatch
from arkflow_tpu.components import Processor, ensure_plugins_loaded
from arkflow_tpu.components.base import Resource
from arkflow_tpu.components.registry import build_component
from arkflow_tpu.errors import ConfigError, ConnectError
from arkflow_tpu.runtime.cluster import (
    WORKER_ROLES,
    ClusterDispatcher,
    ClusterWorkerServer,
    RemoteWorker,
    kv_export_from_wire,
    kv_export_to_wire,
    parse_remote_tpu_config,
    parse_worker_config,
)
from arkflow_tpu.runtime.fleet import FleetController, parse_fleet_config

ensure_plugins_loaded()

TINY = dict(vocab_size=128, dim=64, layers=2, heads=4, kv_heads=2, ffn=96,
            max_seq=64)


# -- kv_push wire codec (no jax) --------------------------------------------


def _fake_export(shards=1, dtype="bfloat16", pages=3):
    """A synthetic prefill_export payload: deterministic slabs in the pool
    layout [layers, pages, page, kv_heads/shards, dh]."""
    import ml_dtypes

    dt = (np.dtype(ml_dtypes.bfloat16) if dtype == "bfloat16"
          else np.dtype(dtype))
    shape = (2, pages, 4, 2, 16)
    rng = np.random.default_rng(7)
    k = [rng.standard_normal(shape).astype(dt) for _ in range(shards)]
    v = [rng.standard_normal(shape).astype(dt) for _ in range(shards)]
    return {"prompt": [3, 17, 42, 7, 91], "max_new_tokens": 6,
            "first_token": 11, "tokens": [11], "page_size": 4,
            "shards": shards, "dtype": dtype, "k": k, "v": v}


def test_kv_wire_roundtrip_is_bitwise():
    exp = _fake_export(shards=1)
    meta, frames = kv_export_to_wire(exp)
    # the metadata must survive the JSON hop the flight frame puts it through
    meta = json.loads(json.dumps(meta))
    assert len(frames) == 2
    back = kv_export_from_wire(meta, frames)
    assert back["prompt"] == exp["prompt"]
    assert back["first_token"] == 11 and back["max_new_tokens"] == 6
    for side in ("k", "v"):
        for a, b in zip(exp[side], back[side]):
            assert b.dtype == a.dtype and b.shape == a.shape
            assert b.tobytes() == a.tobytes()  # bitwise, not approx


def test_kv_wire_ships_one_frame_per_tp_shard():
    exp = _fake_export(shards=2)
    meta, frames = kv_export_to_wire(exp)
    assert meta["shards"] == 2 and len(frames) == 4  # K x2 then V x2
    back = kv_export_from_wire(json.loads(json.dumps(meta)), frames)
    assert back["k"][1].tobytes() == exp["k"][1].tobytes()
    assert back["v"][0].tobytes() == exp["v"][0].tobytes()


def test_kv_wire_done_export_ships_no_pages():
    meta, frames = kv_export_to_wire(
        {"prompt": [5], "max_new_tokens": 4, "done": True, "tokens": []})
    assert meta["done"] is True and frames == []
    assert kv_export_from_wire(meta, [])["done"] is True


def test_kv_wire_frame_count_mismatch_raises():
    exp = _fake_export(shards=2)
    meta, frames = kv_export_to_wire(exp)
    with pytest.raises(ConnectError, match="slab frames"):
        kv_export_from_wire(meta, frames[:3])


# -- RemoteWorker occupancy + role routing (no jax) -------------------------


def test_remote_worker_ingests_occupancy_and_folds_headroom():
    w = RemoteWorker("arkflow://127.0.0.1:1", "t-disagg-rw")
    w.note_report({"worker_id": "d0", "window": 4, "role": "decode",
                   "gen_slots": 8, "gen_slots_busy": 3,
                   "page_pool_occupancy": 0.4}, now=1.0)
    assert w.role == "decode"
    assert w.gen_slots == 8 and w.gen_slots_busy == 3
    assert w.page_occupancy == 0.4
    assert w.has_headroom()
    rep = w.report()
    assert rep["role"] == "decode" and rep["gen_slots"] == 8
    assert rep["gen_slots_busy"] == 3
    assert rep["page_pool_occupancy"] == 0.4
    # every generation slot busy: saturated regardless of the AIMD window
    w.note_report({"window": 4, "role": "decode", "gen_slots": 8,
                   "gen_slots_busy": 8, "page_pool_occupancy": 0.4}, now=2.0)
    assert not w.has_headroom()
    # page pool nearly full: ditto
    w.note_report({"window": 4, "role": "decode", "gen_slots": 8,
                   "gen_slots_busy": 1, "page_pool_occupancy": 0.97}, now=3.0)
    assert not w.has_headroom()
    # an unknown role from a newer/older peer degrades to 'both'
    w.note_report({"window": 4, "role": "builder"}, now=4.0)
    assert w.role == "both"


def test_remote_worker_serves_roles():
    w = RemoteWorker("arkflow://127.0.0.1:2", "t-disagg-serves")
    for role in WORKER_ROLES:
        w.role = role
        assert w.serves(role)
    w.role = "both"
    assert w.serves("prefill") and w.serves("decode")
    w.role = "prefill"
    assert w.serves("prefill") and not w.serves("decode")


def _mk_dispatcher(n, name, **kw):
    urls = [f"arkflow://127.0.0.1:{9000 + i}" for i in range(n)]
    d = ClusterDispatcher(urls, name=name, heartbeat_s=999, **kw)
    for w in d.workers.values():
        w.alive = True
    return d, urls


def test_decode_targets_order_by_occupancy_and_cap():
    d, urls = _mk_dispatcher(4, "t-disagg-targets", decode_candidates=2)
    a, b, c, p = (d.workers[u] for u in urls)
    p.role = "prefill"  # never a decode target
    for w, (busy, occ) in zip((a, b, c), ((6, 0.2), (2, 0.8), (2, 0.1))):
        w.role = "decode"
        w.gen_slots, w.gen_slots_busy, w.page_occupancy = 8, busy, occ
    got = [w.url for w in d.decode_targets()]
    # least slot pressure first, page pressure breaks the tie, cap at 2
    assert got == [urls[2], urls[1]]
    b.draining = True
    assert [w.url for w in d.decode_targets()] == [urls[2], urls[0]]


def test_plan_role_filter_keeps_prefill_subring_affinity():
    d, urls = _mk_dispatcher(4, "t-disagg-plan")
    d.workers[urls[0]].role = "decode"
    d.workers[urls[2]].role = "decode"
    assert d.role_split()
    full = [w.url for w in d.plan(b"some key")]
    pre = [w.url for w in d.plan(b"some key", role="prefill")]
    # the role walk is the same ring minus the decode members: affinity
    # order among prefill-capable workers is preserved verbatim
    assert pre == [u for u in full if u not in (urls[0], urls[2])]
    assert all(d.workers[u].serves("prefill") for u in pre)
    for u in urls:
        d.workers[u].role = "both"
    assert not d.role_split()


def test_dispatch_has_no_candidates_when_only_decode_workers_live():
    d, urls = _mk_dispatcher(2, "t-disagg-nopre")
    for u in urls:
        d.workers[u].role = "decode"
    assert d.role_split()
    assert d.plan(b"k", role="prefill") == []


# -- config parsing (no jax) ------------------------------------------------


def test_worker_role_parses_and_validates():
    base = {"processors": [{"type": "python",
                            "script": "def process(b): return b"}]}
    _, opts = parse_worker_config(base)
    assert opts["role"] == "both"
    _, opts = parse_worker_config({**base, "worker": {"role": "decode"}})
    assert opts["role"] == "decode"
    with pytest.raises(ConfigError, match="role"):
        parse_worker_config({**base, "worker": {"role": "drafter"}})


def test_remote_tpu_decode_candidates_parse():
    base = {"type": "remote_tpu", "workers": ["arkflow://h:1"]}
    assert parse_remote_tpu_config(base)["decode_candidates"] == 3
    assert parse_remote_tpu_config(
        {**base, "decode_candidates": 1})["decode_candidates"] == 1
    with pytest.raises(ConfigError, match="decode_candidates"):
        parse_remote_tpu_config({**base, "decode_candidates": 0})


def test_fleet_roles_parse_and_one_sided_guard():
    cfg = parse_fleet_config({
        "min_workers": 1, "max_workers": 4,
        "template": {"processors": [{"type": "python",
                                     "script": "def process(b): return b"}]},
        "roles": {"prefill": {"min": 1, "max": 2},
                  "decode": {"min": 1, "max": 2}}})
    assert cfg.roles == {"prefill": (1, 2), "decode": (1, 2)}
    assert cfg.report()["roles"]["decode"] == {"min": 1, "max": 2}
    base = {"min_workers": 1, "max_workers": 4,
            "template": {"processors": [{"type": "python",
                                         "script": "def process(b): return b"}]}}
    with pytest.raises(ConfigError, match="unknown role"):
        parse_fleet_config({**base, "roles": {"drafter": {"min": 1}}})
    with pytest.raises(ConfigError, match="min"):
        parse_fleet_config({**base, "roles": {"both": {"min": -1}}})
    # a split that can never serve one side is dead on arrival
    with pytest.raises(ConfigError, match="one-sided"):
        parse_fleet_config({**base, "roles": {"prefill": {"min": 1, "max": 2}}})
    with pytest.raises(ConfigError, match="one-sided"):
        parse_fleet_config({**base, "roles": {
            "decode": {"min": 1, "max": 2}, "both": {"min": 0, "max": 0}}})
    # 'both' capacity alone covers either side
    assert parse_fleet_config({**base, "roles": {"both": {"min": 1, "max": 2}}}
                              ).roles == {"both": (1, 2)}


def test_shipped_disagg_worker_templates_parse():
    """examples/workers/ configs are worker-shaped (outside the engine
    example glob): the disagg templates must parse with their roles."""
    import yaml

    root = Path(__file__).parent.parent / "examples/workers"
    procs, opts = parse_worker_config(
        yaml.safe_load((root / "prefill_worker.yaml").read_text()))
    assert procs[0]["type"] == "tpu_generate" and opts["role"] == "prefill"
    procs, opts = parse_worker_config(
        yaml.safe_load((root / "decode_worker.yaml").read_text()))
    assert procs[0]["type"] == "tpu_generate" and opts["role"] == "decode"


# -- per-role fleet scaling (no jax; echo workers, fake clock) --------------


class _Echo(Processor):
    async def process(self, batch):
        return [batch]


async def _start_echo(worker_id, **kw):
    srv = ClusterWorkerServer([_Echo()], host="127.0.0.1", port=0,
                              worker_id=worker_id, **kw)
    await srv.connect()
    await srv.start()
    return srv


def _wurl(srv):
    return f"arkflow://127.0.0.1:{srv.port}"


class _RoleSpawner:
    """Role-aware spawner double: launches real in-process workers with the
    requested role so adopt probes ingest it from the register report."""

    def __init__(self):
        self.roles: list = []  # role passed to each spawn (None = role-blind)
        self.retired: list[str] = []
        self.servers: dict[str, ClusterWorkerServer] = {}
        self._owned: set[str] = set()

    async def spawn(self, shapes=(), role=None):
        self.roles.append(role)
        srv = await _start_echo(f"spawned-{len(self.roles)}",
                                role=role or "both")
        url = _wurl(srv)
        self.servers[url] = srv
        self._owned.add(url)
        return url

    def owns(self, url):
        return url in self._owned

    def reap(self, url):
        self._owned.discard(url)

    async def retire(self, url, *, grace_s=30.0):
        self.retired.append(url)
        srv = self.servers.pop(url, None)
        self._owned.discard(url)
        if srv is not None:
            await srv.stop()

    async def close(self):
        for url in list(self.servers):
            await self.retire(url)


def _role_cfg(**overrides):
    block = {"min_workers": 1, "max_workers": 4, "interval": "100ms",
             "scale_out_sustain": "5s", "scale_in_sustain": "5s",
             "cooldown": "1ms",
             "template": {"processors": [
                 {"type": "python", "script": "def process(b): return b"}]},
             "roles": {"prefill": {"min": 1, "max": 2},
                       "decode": {"min": 1, "max": 1}}}
    block.update(overrides)
    return parse_fleet_config(block, static_workers=2, who="test")


def test_fleet_respawns_departed_role_at_its_floor():
    async def go():
        pre = await _start_echo("static-pre", role="prefill")
        dec = await _start_echo("static-dec", role="decode")
        d = ClusterDispatcher([_wurl(pre), _wurl(dec)],
                              name="t-roles-respawn", heartbeat_s=999)
        sp = _RoleSpawner()
        clk = {"t": 0.0}
        fc = FleetController(d, sp, _role_cfg(), name="t-roles-respawn",
                             clock=lambda: clk["t"])
        try:
            await d.start()
            assert d.workers[_wurl(dec)].role == "decode"
            await dec.stop()  # the decode side is preempted
            d.workers[_wurl(dec)].note_down(ConnectError("stale"))
            ev = await fc.tick()
            assert ev is not None and ev["action"] == "respawn"
            assert "role 'decode'" in ev["reason"]
            assert sp.roles == ["decode"]
            assert d.workers[ev["worker"]].role == "decode"
        finally:
            await fc.close()
            await d.close()
            await pre.stop()

    asyncio.run(asyncio.wait_for(go(), timeout=20))


def test_fleet_scales_out_pressured_role_and_caps_at_role_max():
    async def go():
        pre = await _start_echo("static-pre", role="prefill")
        dec = await _start_echo("static-dec", role="decode")
        pre_url, dec_url = _wurl(pre), _wurl(dec)
        d = ClusterDispatcher([pre_url, dec_url], name="t-roles-out",
                              heartbeat_s=999)
        sp = _RoleSpawner()
        clk = {"t": 0.0}
        fc = FleetController(d, sp, _role_cfg(), name="t-roles-out",
                             clock=lambda: clk["t"])
        try:
            await d.start()
            # prefill tier exhausted, decode tier idle: only prefill grows
            w = d.workers[pre_url]
            w.inflight = w.window
            assert await fc.tick() is None  # pressure clock starts
            clk["t"] = 6.0
            w.inflight = w.window
            ev = await fc.tick()
            assert ev is not None and ev["action"] == "scale_out"
            assert "role 'prefill'" in ev["reason"]
            assert sp.roles == ["prefill"]
            assert d.workers[ev["worker"]].role == "prefill"
            # decode pressure at its role max (1) caps instead of growing
            clk["t"] = 12.0
            wd = d.workers[dec_url]
            wd.gen_slots, wd.gen_slots_busy = 4, 4
            assert await fc.tick() is None
            clk["t"] = 18.0
            wd.gen_slots, wd.gen_slots_busy = 4, 4
            assert await fc.tick() is None
            events = [e["action"] for e in fc.report()["events"]]
            assert "scale_out_capped" in events
            assert sp.roles == ["prefill"]  # no decode spawn happened
        finally:
            await fc.close()
            await d.close()
            await pre.stop()
            await dec.stop()

    asyncio.run(asyncio.wait_for(go(), timeout=20))


# -- page export/adopt on the generation server (jax, tiny, CPU) ------------


def _gen_setup(seed=0):
    import jax

    from arkflow_tpu.models import get_model

    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(seed), cfg)
    return fam, cfg, params


def _mk_server(params, cfg, **kw):
    from arkflow_tpu.tpu.serving import GenerationServer

    kw.setdefault("slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("max_seq", 64)
    return GenerationServer(params, cfg, **kw)


PROMPTS = [[3, 17, 42, 7, 91, 8], [9, 4], list(range(40, 55))]


def test_export_adopt_matches_local_decode():
    """prefill_export -> wire -> generate_from_pages must emit exactly the
    tokens a local generate() produces — one-shot and chunked prefill, with
    a partially-filled last page (prompt lengths not page multiples) and a
    non-contiguous table on both sides (prefix-cache churn scatters the
    free list before the disagg requests run)."""
    _, cfg, params = _gen_setup()

    async def go():
        pre = _mk_server(params, cfg, prefix_cache_pages=4)
        dec = _mk_server(params, cfg, prefix_cache_pages=4)
        ref = _mk_server(params, cfg)
        # churn both pools first so the disagg pages come out scattered
        await pre.generate([5, 6, 7, 8, 9], max_new_tokens=3)
        await dec.generate([1, 3, 5], max_new_tokens=3)
        local = [await ref.generate(p, max_new_tokens=6) for p in PROMPTS]
        got = []
        for p in PROMPTS:
            exp = await pre.prefill_export(p, max_new_tokens=6)
            meta, frames = kv_export_to_wire(exp)
            back = kv_export_from_wire(json.loads(json.dumps(meta)), frames)
            # the hop is bitwise: what decode adopts IS what prefill wrote
            for side in ("k", "v"):
                for a, b in zip(exp[side], back[side]):
                    assert b.tobytes() == a.tobytes()
            got.append(await dec.generate_from_pages(back))
        assert got == local
        # chunked prefill exports through the same path
        pre2 = _mk_server(params, cfg, prefill_chunk=4)
        exp = await pre2.prefill_export(PROMPTS[2], max_new_tokens=6)
        assert (await dec.generate_from_pages(exp)) == local[2]
        # prefill-side TTFT stamped at export; adopted requests never
        # double-stamp on the decode side
        assert pre.health_report().get("ttft", {}).get("count", 0) >= 2
        assert "ttft" not in dec.health_report() or \
            dec.health_report()["ttft"]["count"] == 1  # its own generate()
        for s in (pre, dec, ref, pre2):
            await s.close()

    asyncio.run(asyncio.wait_for(go(), timeout=120))


def test_adopt_rejects_mismatched_geometry():
    _, cfg, params = _gen_setup()

    async def go():
        pre = _mk_server(params, cfg)
        dec = _mk_server(params, cfg, page_size=8)
        exp = await pre.prefill_export([3, 17, 42, 7, 91], max_new_tokens=4)
        with pytest.raises(ConfigError, match="page_size"):
            await dec.generate_from_pages(exp)
        bad = dict(exp)
        bad["k"] = [a[:, :1] for a in exp["k"]]  # truncated page axis
        bad["v"] = [a[:, :1] for a in exp["v"]]
        dec2 = _mk_server(params, cfg)
        with pytest.raises(ConfigError, match="geometry"):
            await dec2.generate_from_pages(bad)
        for s in (pre, dec, dec2):
            await s.close()

    asyncio.run(asyncio.wait_for(go(), timeout=120))


def test_tp2_hostmesh_export_adopts_shard_per_frame():
    """tp=2 pools export one slab frame per shard (split over kv_heads);
    adopting into another tp=2 pool reproduces the single-chip tokens."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    from arkflow_tpu.parallel.mesh import MeshSpec, create_mesh, shard_params

    fam, cfg, params = _gen_setup(seed=3)
    mesh = create_mesh(MeshSpec(tp=2), devices=jax.devices()[:2])
    axes = {name: name for name in mesh.axis_names}
    sharded = shard_params(params, fam.param_specs(cfg, axes), mesh)

    async def go():
        ref = _mk_server(params, cfg)
        local = [await ref.generate(p, max_new_tokens=5) for p in PROMPTS]
        pre = _mk_server(sharded, cfg, mesh=mesh)
        dec = _mk_server(sharded, cfg, mesh=mesh)
        got = []
        for p in PROMPTS:
            exp = await pre.prefill_export(p, max_new_tokens=5)
            assert exp["shards"] == 2
            meta, frames = kv_export_to_wire(exp)
            assert len(frames) == 4  # K, V x 2 shards: one frame per shard
            back = kv_export_from_wire(json.loads(json.dumps(meta)), frames)
            got.append(await dec.generate_from_pages(back))
        assert got == local
        for s in (ref, pre, dec):
            await s.close()

    asyncio.run(asyncio.wait_for(go(), timeout=180))


_CHILD_PREFILL = textwrap.dedent("""
    import asyncio, json, sys
    import numpy as np
    import jax
    from arkflow_tpu.models import get_model
    from arkflow_tpu.runtime.cluster import kv_export_to_wire
    from arkflow_tpu.tpu.serving import GenerationServer

    TINY = dict(vocab_size=128, dim=64, layers=2, heads=4, kv_heads=2,
                ffn=96, max_seq=64)
    fam = get_model("decoder_lm")
    cfg = fam.make_config(**TINY)
    params = fam.init(jax.random.PRNGKey(0), cfg)

    async def go():
        srv = GenerationServer(params, cfg, slots=2, page_size=4, max_seq=64)
        exp = await srv.prefill_export([3, 17, 42, 7, 91, 8],
                                       max_new_tokens=6)
        await srv.close()
        return exp

    exp = asyncio.run(go())
    meta, frames = kv_export_to_wire(exp)
    out = sys.argv[1]
    with open(out + "/meta.json", "w") as f:
        json.dump(meta, f)
    for i, fr in enumerate(frames):
        with open(f"{out}/frame{i}.bin", "wb") as f:
            f.write(fr)
""")


def test_kv_pages_adopt_bitwise_across_processes(tmp_path):
    """Satellite: the full serialize -> other-process -> adopt path. A
    child process prefills and writes the wire frames; this process adopts
    them and must decode argmax-identically to a local prefill (same seed
    -> same params on both sides)."""
    from arkflow_tpu.utils.cleanenv import pin_cpu_env

    env = dict(os.environ)
    pin_cpu_env(env)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent)
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD_PREFILL, str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    meta = json.loads((tmp_path / "meta.json").read_text())
    frames = [(tmp_path / f"frame{i}.bin").read_bytes()
              for i in range(2 * meta["shards"])]
    export = kv_export_from_wire(meta, frames)

    _, cfg, params = _gen_setup(seed=0)

    async def go():
        ref = _mk_server(params, cfg)
        local = await ref.generate([3, 17, 42, 7, 91, 8], max_new_tokens=6)
        dec = _mk_server(params, cfg)
        got = await dec.generate_from_pages(export)
        await ref.close()
        await dec.close()
        return local, got

    local, got = asyncio.run(asyncio.wait_for(go(), timeout=120))
    assert got == local


def test_ttft_histogram_in_health_report():
    _, cfg, params = _gen_setup()

    async def go():
        srv = _mk_server(params, cfg)
        assert "ttft" not in srv.health_report()  # no samples yet
        await asyncio.gather(
            srv.generate([3, 5, 7], max_new_tokens=4),
            srv.generate([11, 13], max_new_tokens=4))
        rep = srv.health_report()
        assert rep["ttft"]["count"] == 2
        assert 0.0 < rep["ttft"]["p50_ms"] <= rep["ttft"]["p99_ms"]
        await srv.close()

    asyncio.run(asyncio.wait_for(go(), timeout=120))


# -- acceptance: the disagg soak (fast tier-1 mode) -------------------------


def test_chaos_soak_disagg_fast_mode_smoke():
    """Acceptance gate (tools/chaos_soak.py --disagg --fast): real
    role-split generation worker subprocesses — both layouts deliver every
    request at equal worker count, every KV page flows cross-process,
    duplicate prompts stick to ONE prefill worker, and a mid-stream decode
    SIGKILL loses nothing (nack -> redelivery -> re-prefill) with the
    restarted worker adopting pages again. The disaggregated layout's win on
    TTFT p99 and tokens/sec is two ratios of wall-clock rates of CPU
    workers: the verdict reports them, the full soak holds them, this smoke
    does not (they turned it red under six busy test workers)."""
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
    try:
        from chaos_soak import run_disagg_soak
    finally:
        sys.path.pop(0)

    verdict = run_disagg_soak(seconds=60.0, seed=7, fast=True)
    assert verdict["pass"], verdict
    perf = verdict["perf"]
    assert perf["disagg_ttft_p99_ms"] > 0.0 and perf["cohosted_ttft_p99_ms"] > 0.0
    assert perf["cohosted_delivered"] == perf["disagg_delivered"] > 0
    assert perf["kv_pushed"] == perf["kv_adopted"] > 0
    assert verdict["affinity"]["one_prefill_took_all"]
    chaos = verdict["chaos"]
    assert chaos["killed"] and chaos["revived"] and chaos["adopts_again"]
    assert chaos["lost_rows"] == 0 and chaos["identity_ok"]


# -- end-to-end disaggregated cluster (jax; in-process worker fleet) --------


def _gen_proc_cfg():
    return {"type": "tpu_generate", "model": "decoder_lm",
            "model_config": {k: v for k, v in TINY.items()
                             if k != "max_seq"},
            "serving": "continuous", "slots": 4, "page_size": 4,
            "max_input": 32, "max_new_tokens": 8, "eos_id": 2, "seed": 3,
            "prefix_cache_pages": 8}


async def _start_gen_worker(worker_id, role):
    proc = build_component("processor", _gen_proc_cfg(), Resource())
    srv = ClusterWorkerServer([proc], host="127.0.0.1", port=0,
                              worker_id=worker_id, max_in_flight=2,
                              role=role)
    await srv.connect()
    await srv.start()
    return srv


PAYLOADS = [b"the quick brown fox", b"hello world", b"a b c d e f g"]


def test_disagg_cluster_end_to_end_matches_cohosted():
    """The tentpole, end to end: a role-split fleet (prefill worker pushing
    KV pages to occupancy-picked decode workers) must emit exactly what a
    co-hosted fleet emits, refuse kv_push retryably on a draining or
    role-mismatched receiver with the prefill side re-planning to the next
    candidate, and advertise decode occupancy + TTFT in heartbeats."""
    async def go():
        both = await _start_gen_worker("w-both", "both")
        d_ref = ClusterDispatcher([_wurl(both)], name="t-disagg-ref",
                                  heartbeat_s=999)
        await d_ref.start()
        ref_out = []
        for p in PAYLOADS:
            out = await d_ref.dispatch(MessageBatch.new_binary([p]))
            ref_out.append(out[0].to_binary("generated")[0])
        await d_ref.close()
        await both.stop()

        pre = await _start_gen_worker("w-pre", "prefill")
        dec1 = await _start_gen_worker("w-dec1", "decode")
        dec2 = await _start_gen_worker("w-dec2", "decode")
        d = ClusterDispatcher([_wurl(pre), _wurl(dec1), _wurl(dec2)],
                              name="t-disagg-e2e", heartbeat_s=999)
        try:
            await d.start()
            assert d.role_split()
            # steer placement: dec1 looks busier, dec2 must be tried first
            d.workers[_wurl(dec1)].page_occupancy = 0.5
            got = []
            for p in PAYLOADS:
                out = await d.dispatch(MessageBatch.new_binary([p]))
                got.append(out[0].to_binary("generated")[0])
            assert got == ref_out  # disagg changes placement, not tokens
            assert pre._kv_pushed == len(PAYLOADS)
            assert dec2._kv_adopted == len(PAYLOADS)
            assert dec1._kv_adopted == 0

            # heartbeat refresh surfaces decode occupancy + prefill TTFT
            rep = dec2.load_report()
            assert rep["role"] == "decode" and rep["gen_slots"] == 4
            assert "page_pool_occupancy" in rep
            assert pre.load_report()["ttft_p99_ms"] > 0.0

            # a draining decode worker refuses kv_push RETRYABLY and the
            # prefill side re-plans to the next candidate mid-request
            dec2.draining = True  # server-side only: dispatcher is stale
            d.workers[_wurl(dec1)].page_occupancy = 0.0
            d.workers[_wurl(dec2)].page_occupancy = 0.0
            # ordering tie falls to inflight/url; force dec2 first so the
            # refusal actually fires before the healthy candidate
            d.workers[_wurl(dec1)].page_occupancy = 0.2
            out = await d.dispatch(MessageBatch.new_binary([PAYLOADS[0]]))
            assert out[0].to_binary("generated")[0] == ref_out[0]
            assert dec2._kv_refused >= 1
            assert pre._kv_push_retries >= 1
            assert dec1._kv_adopted >= 1
            dec2.draining = False

            # role mismatch refuses the same way: a push aimed at a
            # prefill worker re-plans to the ring's next (decode) candidate
            gen = pre._generation_server()
            exp = await gen.prefill_export([7, 9, 11], max_new_tokens=4)
            retries0 = pre._kv_push_retries
            tokens = await pre._push_export(exp, [_wurl(pre), _wurl(dec1)])
            assert pre._kv_refused >= 1  # refused its own mirrored push
            assert pre._kv_push_retries == retries0 + 1
            assert tokens  # dec1 finished the request

            # every candidate refusing surfaces as ConnectError (nack ->
            # redelivery re-prefills), never a silent loss
            dec1.draining = True
            exp2 = await gen.prefill_export([5, 3], max_new_tokens=4)
            with pytest.raises(ConnectError, match="no decode worker"):
                await pre._push_export(exp2, [_wurl(dec1)])
            dec1.draining = False

            # decode-role workers are not infer candidates at all
            only_dec = ClusterDispatcher([_wurl(dec1)], name="t-disagg-nop",
                                         heartbeat_s=999)
            await only_dec.start()
            assert only_dec.role_split()
            with pytest.raises(ConnectError, match="no live cluster worker"):
                await only_dec.dispatch(MessageBatch.new_binary([b"x"]))
            await only_dec.close()
        finally:
            await d.close()
            for srv in (pre, dec1, dec2):
                await srv.stop()

    asyncio.run(asyncio.wait_for(go(), timeout=600))
