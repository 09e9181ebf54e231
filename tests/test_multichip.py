"""Multi-chip serving: dp-sharded dispatch + replicated device pool, the two
forms ``tpu_inference`` serves several chips in (pipelined segmentation,
``mesh: {pp}``, was removed in PR 45 and is refused by name).

Runs on the 8-device virtual CPU platform conftest pins
(``--xla_force_host_platform_device_count=8``): real multi-device shardings,
no TPU required. Covers the ISSUE-3 acceptance points: (a) dp-sharded outputs
bitwise-identical to single-device, (b) dp-scaled buckets divide evenly and
the coalescer emits them exactly, (c) the device pool round-robins and keeps
at-least-once delivery when a member runner is fault-injected.
"""

import asyncio

import numpy as np
import pytest

import jax

from arkflow_tpu.errors import ConfigError
from arkflow_tpu.tpu.bucketing import BucketPolicy

TINY_BERT = {"vocab_size": 512, "hidden": 32, "layers": 2, "heads": 4,
             "ffn": 64, "max_positions": 64, "num_labels": 2}


def _tiny_inputs(n=8, seq=16, seed=3):
    rng = np.random.RandomState(seed)
    return {"input_ids": rng.randint(1, 512, (n, seq)).astype(np.int32),
            "attention_mask": np.ones((n, seq), np.int32)}


def _need_devices(n):
    if len(jax.devices()) < n:
        pytest.skip(f"needs {n} virtual devices")


# -- (b) dp-aware bucket policy -------------------------------------------


def test_bucket_policy_dp_scaled():
    pol = BucketPolicy((8, 16, 32), (32, 64))
    scaled = pol.dp_scaled(4)
    assert scaled.batch_buckets == (32, 64, 128)
    assert scaled.seq_buckets == (32, 64)  # seq dim untouched by dp
    # every global bucket divides evenly into per-chip shards ON the
    # original grid — the property the sharded dispatch relies on
    for g, p in zip(scaled.batch_buckets, pol.batch_buckets):
        assert g % 4 == 0 and g // 4 == p
    assert pol.dp_scaled(1) is pol
    with pytest.raises(ConfigError):
        pol.dp_scaled(0)


def test_dp_runner_scales_its_buckets():
    _need_devices(4)
    from arkflow_tpu.parallel.mesh import MeshSpec
    from arkflow_tpu.tpu.runner import ModelRunner

    r = ModelRunner("bert_classifier", TINY_BERT,
                    buckets=BucketPolicy((4, 8), (16,)),
                    mesh_spec=MeshSpec(dp=4))
    assert r.buckets.batch_buckets == (16, 32)
    assert all(b % 4 == 0 for b in r.buckets.batch_buckets)


def test_coalesce_dp_scaled_grid_emissions():
    """Memory buffer ``coalesce: {dp: N}`` targets the dp-scaled grid: every
    steady-state emission is exactly per-chip-bucket x dp rows."""
    from arkflow_tpu.components import (NoopAck, Resource, ensure_plugins_loaded)
    from arkflow_tpu.components.registry import build_component
    from arkflow_tpu.batch import MessageBatch

    ensure_plugins_loaded()
    buf = build_component(
        "buffer",
        {"type": "memory", "capacity": 64, "timeout": "5ms",
         "coalesce": {"batch_buckets": [4, 8], "dp": 4, "deadline": "5ms"}},
        Resource())
    assert buf._coalescer.buckets == (16, 32)
    assert buf._coalescer.target == 32

    async def go():
        # 40 rows in ragged writes: one bucket-exact 32-row emission, then a
        # deadline flush carving the 8-row tail against the scaled grid
        for n in (10, 6, 16, 8):
            await buf.write(MessageBatch.new_binary([b"x"] * n), NoopAck())
        first = await buf.read()
        await buf.close()
        second = await buf.read()
        return first[0].num_rows, second[0].num_rows

    rows_a, rows_b = asyncio.run(go())
    assert rows_a == 32 and rows_a % 4 == 0  # bucket-exact on the scaled grid
    # the 8-row tail is below the smallest scaled bucket (16): close()
    # flushes it merged rather than padding it up — the runner's dp-scaled
    # policy pads it to 16 at dispatch, same as single-device sub-bucket rows
    assert rows_b == 8


def test_coalesce_dp_validation():
    from arkflow_tpu.components import Resource, ensure_plugins_loaded
    from arkflow_tpu.components.registry import build_component

    ensure_plugins_loaded()
    with pytest.raises(ConfigError, match="dp"):
        build_component(
            "buffer",
            {"type": "memory", "capacity": 64, "timeout": "5ms",
             "coalesce": {"batch_buckets": [4], "dp": 0, "deadline": "5ms"}},
            Resource())


# -- (a) dp-sharded dispatch parity ---------------------------------------


def test_dp_sharded_outputs_bitwise_identical():
    _need_devices(4)
    from arkflow_tpu.parallel.mesh import MeshSpec
    from arkflow_tpu.tpu.runner import ModelRunner

    buckets = BucketPolicy((8,), (16,))
    inputs = _tiny_inputs()
    single = ModelRunner("bert_classifier", TINY_BERT, buckets=buckets,
                         devices=[jax.devices()[0]])
    sharded = ModelRunner("bert_classifier", TINY_BERT, buckets=buckets,
                          mesh_spec=MeshSpec(dp=4))
    a = single.infer_sync(inputs)
    b = sharded.infer_sync(inputs)
    assert set(a) == set(b)
    for k in a:
        # batch-dim sharding must not change per-row math AT ALL: same
        # program per shard, rows merely partitioned — bitwise, not allclose
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))


def test_dp_sharded_async_prefetch_parity(monkeypatch):
    """The pipelined path (eager SHARDED device_put prefetch outside the
    in-flight semaphore) serves the same bytes, and the PR-2 wins report
    active through the metrics gauges."""
    _need_devices(4)
    monkeypatch.setenv("ARKFLOW_PREFETCH", "1")
    from arkflow_tpu.parallel.mesh import MeshSpec
    from arkflow_tpu.tpu.runner import ModelRunner

    buckets = BucketPolicy((8,), (16,))
    inputs = _tiny_inputs()
    single = ModelRunner("bert_classifier", TINY_BERT, buckets=buckets,
                         devices=[jax.devices()[0]])
    sharded = ModelRunner("bert_classifier", TINY_BERT, buckets=buckets,
                          mesh_spec=MeshSpec(dp=4))
    assert sharded._prefetch and sharded.mesh is not None
    assert sharded.m_prefetch_on.value == 1  # assertable via metrics
    # donation is platform-gated (CPU has none) but must be WIRED under the
    # mesh: the gauge exists and reflects the gate, not a hard-disable
    assert sharded.m_donate_on.value == 0
    ref = single.infer_sync(inputs)

    async def go():
        outs = await asyncio.gather(*[sharded.infer(inputs) for _ in range(3)])
        return outs

    for out in asyncio.run(go()):
        np.testing.assert_array_equal(np.asarray(ref["logits"]),
                                      np.asarray(out["logits"]))


def test_mesh_prefetch_env_gates(monkeypatch):
    """Under a mesh the prefetch/donate knobs behave exactly as on a single
    device: platform-gated defaults, env force/kill overrides — no more
    hard-disable the moment a mesh exists."""
    _need_devices(2)
    from arkflow_tpu.parallel.mesh import MeshSpec
    from arkflow_tpu.tpu.runner import ModelRunner

    buckets = BucketPolicy((8,), (16,))
    monkeypatch.setenv("ARKFLOW_PREFETCH", "0")
    r = ModelRunner("bert_classifier", TINY_BERT, buckets=buckets,
                    mesh_spec=MeshSpec(dp=2))
    assert r._prefetch is False and r.m_prefetch_on.value == 0
    monkeypatch.setenv("ARKFLOW_PREFETCH", "1")
    r2 = ModelRunner("bert_classifier", TINY_BERT, buckets=buckets,
                     mesh_spec=MeshSpec(dp=2))
    assert r2._prefetch is True and r2.m_prefetch_on.value == 1
    assert r2._donate is False  # CPU mesh: donation stays platform-gated


# -- (c) replicated device pool -------------------------------------------


def test_pool_round_robins_least_loaded():
    _need_devices(4)
    from arkflow_tpu.tpu.pool import ModelRunnerPool
    from arkflow_tpu.tpu.runner import ModelRunner

    pool = ModelRunnerPool("bert_classifier", TINY_BERT, pool_size=4,
                           buckets=BucketPolicy((8,), (16,)))
    single = ModelRunner("bert_classifier", TINY_BERT,
                         buckets=BucketPolicy((8,), (16,)),
                         devices=[jax.devices()[0]])
    inputs = _tiny_inputs()
    ref = single.infer_sync(inputs)
    base = [int(c.value) for c in pool.m_dispatch]

    async def go():
        return await asyncio.gather(*[pool.infer(inputs) for _ in range(8)])

    for out in asyncio.run(go()):
        np.testing.assert_array_equal(np.asarray(ref["label"]),
                                      np.asarray(out["label"]))
    counts = [int(c.value) - b for c, b in zip(pool.m_dispatch, base)]
    assert counts == [2, 2, 2, 2]  # strict turns among equal-load members


def test_pool_failover_preserves_result():
    _need_devices(2)
    from arkflow_tpu.tpu.pool import ModelRunnerPool

    pool = ModelRunnerPool("bert_classifier", TINY_BERT, pool_size=2,
                           buckets=BucketPolicy((8,), (16,)))
    inputs = _tiny_inputs()
    ref = pool.infer_sync(inputs)

    async def down(_inputs):
        raise RuntimeError("chip down")

    pool.members[0].infer = down
    pool._rr = 0  # pin the cursor so the poisoned member is picked first
    before = pool.m_failover.value
    out = asyncio.run(pool.infer(inputs))
    np.testing.assert_array_equal(np.asarray(ref["label"]),
                                  np.asarray(out["label"]))
    assert pool.m_failover.value == before + 1


def test_pool_config_error_not_retried():
    _need_devices(2)
    from arkflow_tpu.tpu.pool import ModelRunnerPool

    pool = ModelRunnerPool("bert_classifier", TINY_BERT, pool_size=2,
                           buckets=BucketPolicy((8,), (16,)))
    before = pool.m_failover.value
    with pytest.raises(ConfigError):
        # missing model input: deterministic, must NOT burn a failover sweep
        asyncio.run(pool.infer({"input_ids": np.ones((2, 4), np.int32)}))
    assert pool.m_failover.value == before


def test_pool_mesh_mutually_exclusive():
    from arkflow_tpu.components import Resource, ensure_plugins_loaded
    from arkflow_tpu.components.registry import build_component

    ensure_plugins_loaded()
    with pytest.raises(ConfigError, match="mutually exclusive"):
        build_component(
            "processor",
            {"type": "tpu_inference", "model": "bert_classifier",
             "model_config": TINY_BERT, "device_pool": 2, "mesh": {"dp": 2}},
            Resource())


def test_pool_stream_at_least_once_under_member_faults():
    """Full stream: fault-wrapped broker input (redeliver_unacked) feeding a
    device_pool processor whose members BOTH get fault-injected one-shot
    failures. Batch 1 exhausts the pool (error -> stream nack -> broker
    redelivery), the redelivery lands on healed members — every row is
    delivered exactly at-least-once and nothing is lost."""
    _need_devices(2)
    from arkflow_tpu.config import StreamConfig
    from arkflow_tpu.runtime import build_stream

    cfg = StreamConfig.from_mapping({
        "name": "mc-pool-faults",
        "input": {
            "type": "fault",
            "redeliver_unacked": True,
            "inner": {"type": "memory",
                      "messages": ["row a", "row b", "row c", "row d"]},
        },
        "pipeline": {
            # one worker: batch 1 must deterministically sweep BOTH armed
            # members (fail -> failover -> fail -> stream error); concurrent
            # workers could split the two one-shots across batches
            "thread_num": 1,
            "max_delivery_attempts": 4,
            "processors": [
                {"type": "tpu_inference", "model": "bert_classifier",
                 "model_config": TINY_BERT, "max_seq": 16,
                 "device_pool": 2,
                 "batch_buckets": [8], "seq_buckets": [16]},
            ],
        },
        "output": {"type": "drop"},
    })
    stream = build_stream(cfg)
    pool = stream.pipeline.processors[0].runner
    # fault-inject every member once: the first batch must exhaust the pool
    for member in pool.members:
        real_infer = member.infer
        state = {"armed": True}

        async def flaky(inputs, _real=real_infer, _state=state):
            if _state["armed"]:
                _state["armed"] = False
                raise RuntimeError("injected member fault")
            return await _real(inputs)

        member.infer = flaky

    asyncio.run(asyncio.wait_for(stream.run(asyncio.Event()), timeout=60))
    assert stream.m_rows_out.value >= 4  # every source row delivered
    assert stream.m_errors.value >= 1  # the exhausted-pool batch was retried


# -- compile accounting under concurrency (satellite) ----------------------


def test_seen_shapes_compile_count_thread_safe():
    from concurrent.futures import ThreadPoolExecutor

    from arkflow_tpu.tpu.runner import ModelRunner

    r = ModelRunner("bert_classifier", TINY_BERT,
                    buckets=BucketPolicy((8,), (16,)),
                    devices=[jax.devices()[0]])
    inputs = _tiny_inputs()
    # the compile counter is label-shared with earlier runners in this test
    # session (registry dedupes on (name, labels)): assert the DELTA
    before = r.m_compiles.value
    with ThreadPoolExecutor(8) as ex:
        list(ex.map(lambda _: r.infer_sync(inputs), range(16)))
    # 16 concurrent first-ish sightings of ONE padded shape: exactly one
    # compile counted (the unsynchronized check-then-add double-counted)
    assert r.m_compiles.value - before == 1


# -- tensor-parallel continuous generation: parse-time validation -----------


def test_generate_mesh_parse_time_validation():
    """config.py validates tpu_generate mesh knobs at parse time — through
    fault.inner chaos wrappers — so --validate catches them before build."""
    from arkflow_tpu.config import StreamConfig

    def stream(proc):
        return {
            "name": "gen-mesh",
            "input": {"type": "memory", "messages": ["x"]},
            "pipeline": {"processors": [proc]},
            "output": {"type": "drop"},
        }

    gen = {"type": "tpu_generate", "model": "decoder_lm",
           "serving": "continuous"}
    # dp > 1 with continuous serving: clear error, even chaos-wrapped
    with pytest.raises(ConfigError, match="batch-split"):
        StreamConfig.from_mapping(stream(
            {"type": "fault", "inner": {**gen, "mesh": {"dp": 2}}}))
    with pytest.raises(ConfigError, match="batch-split"):
        StreamConfig.from_mapping(stream({**gen, "mesh": {"sp": 2}}))
    # tp must divide kv_heads (decoder_lm default kv_heads=4)
    with pytest.raises(ConfigError, match="kv_heads"):
        StreamConfig.from_mapping(stream({**gen, "mesh": {"tp": 3}}))
    with pytest.raises(ConfigError, match="kv_heads"):
        StreamConfig.from_mapping(stream(
            {**gen, "model_config": {"kv_heads": 2}, "mesh": {"tp": 4}}))
    # malformed axis values fail with the knob name
    with pytest.raises(ConfigError, match="mesh.tp"):
        StreamConfig.from_mapping(stream({**gen, "mesh": {"tp": "two"}}))
    # valid tensor-parallel spec parses (batch mode ignores the continuous
    # constraints entirely)
    StreamConfig.from_mapping(stream({**gen, "mesh": {"tp": 2}}))
    StreamConfig.from_mapping(stream(
        {**gen, "serving": "batch", "mesh": {"dp": 2, "tp": 2}}))


# -- the removed pipelined mode is refused wherever it could enter ----------

_PP_STALE_KEYS = {"pp_microbatch_rows": 2, "pp_layer_costs": [1.0, 1.0],
                  "pp_profile": "prof.json"}


def _inference_proc(extra: dict, wrapped: bool = False) -> dict:
    proc = {"type": "tpu_inference", "model": "bert_classifier",
            "model_config": TINY_BERT, "max_seq": 16,
            "batch_buckets": [8], "seq_buckets": [16], **extra}
    if wrapped:  # two chaos wrappers deep: the checks look through the chain
        proc = {"type": "fault", "inner": {"type": "fault", "inner": proc}}
    return proc


_PP_PROCS = {
    "pp2": _inference_proc({"mesh": {"pp": 2}}),
    "dp2_pp2": _inference_proc({"mesh": {"dp": 2, "pp": 2}}),
    **{key: _inference_proc({key: val}) for key, val in _PP_STALE_KEYS.items()},
    "fault_inner_pp2": _inference_proc({"mesh": {"pp": 2}}, wrapped=True),
    "fault_inner_stale_key": _inference_proc({"pp_profile": "p.json"}, wrapped=True),
}


def _build_proc(proc: dict):
    from arkflow_tpu.components import Resource, ensure_plugins_loaded
    from arkflow_tpu.components.registry import build_component

    ensure_plugins_loaded()
    return build_component("processor", proc, Resource())


def _stream_of(proc: dict) -> dict:
    return {"name": "pp-gone",
            "input": {"type": "memory", "messages": ["x"]},
            "pipeline": {"processors": [proc]},
            "output": {"type": "drop"}}


def _refused_by_validate(proc, tmp_path, capsys) -> str:
    """``python -m arkflow_tpu --config ... --validate`` on a stream YAML."""
    import yaml

    from arkflow_tpu.runtime.cli import main

    path = tmp_path / "stream.yaml"
    path.write_text(yaml.safe_dump({"streams": [_stream_of(proc)]}))
    assert main(["--config", str(path), "--validate"]) == 2
    return capsys.readouterr().err


def _refused_by_build(proc, tmp_path, capsys) -> str:
    with pytest.raises(ConfigError) as e:
        _build_proc(proc)
    return str(e.value)


@pytest.mark.parametrize("proc", sorted(_PP_PROCS))
@pytest.mark.parametrize("entry", [_refused_by_validate, _refused_by_build],
                         ids=["validate", "build"])
def test_pp_serving_is_refused_by_name(entry, proc, tmp_path, capsys):
    """``mesh.pp`` > 1 or any key of the removed mode, alone, is one refusal
    that says what serves several chips — never ignored, never served as
    something else; ``--validate`` and a build give the same answer."""
    msg = entry(_PP_PROCS[proc], tmp_path, capsys)
    assert "removed in PR 45" in msg
    assert "mesh: {dp: N}" in msg and "device_pool: N" in msg


@pytest.mark.parametrize("spec", [dict(pp=2), dict(dp=2, pp=2)],
                         ids=["pp2", "dp2_pp2"])
def test_model_runner_refuses_a_pp_mesh(spec):
    from arkflow_tpu.parallel.mesh import MeshSpec
    from arkflow_tpu.tpu.runner import ModelRunner

    with pytest.raises(ConfigError, match=r"removed in PR 45.*dp: N.*device_pool: N"):
        ModelRunner("bert_classifier", TINY_BERT,
                    buckets=BucketPolicy((8,), (16,)), mesh_spec=MeshSpec(**spec))


def test_tpu_train_keeps_its_pp_mesh_and_parallel_exports_what_stays(tmp_path, capsys):
    """Training's GPipe schedule is another processor's: ``tpu_train`` with
    ``mesh: {pp: 2}`` validates as before, and the package exports the
    training half only."""
    import yaml

    import arkflow_tpu.parallel as par
    from arkflow_tpu.parallel import pipeline
    from arkflow_tpu.runtime.cli import main

    path = tmp_path / "train.yaml"
    path.write_text(yaml.safe_dump({"streams": [_stream_of(
        {"type": "tpu_train", "model": "decoder_lm", "mesh": {"pp": 2}})]}))
    assert main(["--config", str(path), "--validate"]) == 0, capsys.readouterr().err
    assert {"MeshSpec", "create_mesh", "shard_params"} <= set(vars(par))
    assert {"make_pp_train_step", "pp_param_specs"} <= set(vars(pipeline))
    gone = {"StagePlan", "plan_stages", "uniform_plan", "make_pp_infer_step",
            "pp_repack_layers", "pp_infer_param_specs", "pp_layer_slot_tables"}
    assert not gone & (set(vars(par)) | set(vars(pipeline)))


# -- one rule for the default in-flight depth --------------------------------


@pytest.mark.parametrize("form,env,want", [
    ("single", None, 2), ("dp", None, 2), ("pool_member", None, 2),
    ("single", "3", 3), ("dp", "3", 3), ("pool_member", "1", 1)])
def test_default_in_flight_depth_is_one_rule(form, env, want, monkeypatch):
    """Two steps in flight whatever serves — one device, a dp mesh, a pool's
    member — unless ``ARKFLOW_INFLIGHT`` says otherwise; an explicit value
    wins over both and is checked."""
    _need_devices(2)
    from arkflow_tpu.parallel.mesh import MeshSpec
    from arkflow_tpu.tpu.pool import ModelRunnerPool
    from arkflow_tpu.tpu.runner import ModelRunner

    if env is None:
        monkeypatch.delenv("ARKFLOW_INFLIGHT", raising=False)
    else:
        monkeypatch.setenv("ARKFLOW_INFLIGHT", env)
    buckets = BucketPolicy((8,), (16,))

    def build(**kw):
        if form == "pool_member":
            pool = ModelRunnerPool("bert_classifier", TINY_BERT, pool_size=2,
                                   buckets=buckets, **kw)
            assert pool.max_in_flight == 2 * pool.members[0].max_in_flight
            return pool.members[1]
        mesh = MeshSpec(dp=2) if form == "dp" else None
        return ModelRunner("bert_classifier", TINY_BERT, buckets=buckets,
                           mesh_spec=mesh, **kw)

    assert build().max_in_flight == want
    if env is not None:
        assert build(max_in_flight=5).max_in_flight == 5  # explicit beats the env
    else:
        with pytest.raises(ConfigError, match="max_in_flight"):
            build(max_in_flight=0)


# -- dp twins of what only the pipelined mode's tests covered under a mesh --


#: ``tpu_inference`` under ``mesh: {dp: 2}``, two rows or four a chip
_DP2_PROC = _inference_proc({"mesh": {"dp": 2}, "batch_buckets": [2, 4]})


def test_dp_hot_swap_identical_weights_serves_identically(tmp_path):
    """A candidate tree is placed with the live tree's shardings
    (``place_params`` under a mesh), so a flip to the same weights serves the
    same bytes before, while the swap rolls, and after it."""
    _need_devices(2)
    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.tpu import checkpoint

    proc = _build_proc({**_DP2_PROC, "outputs": ["label", "score", "logits"]})
    runner = proc.runner
    ck = str(tmp_path / "ck")
    checkpoint.save(ck, runner.params)
    shardings = [x.sharding for x in jax.tree_util.tree_leaves(runner.params)]
    batch = MessageBatch.new_binary([f"swap row {i}".encode() for i in range(7)])

    async def go():
        (before,) = await proc.process(batch)
        old = runner.params
        swap = asyncio.ensure_future(proc.swapper.swap(ck))
        during = [(await proc.process(batch))[0] for _ in range(3)]
        rep = await swap
        assert rep["version"] == 1 and rep["completed"] == 1
        assert runner.params is not old  # really flipped, not a no-op
        (after,) = await proc.process(batch)
        return before, during, after

    before, during, after = asyncio.run(go())
    assert [x.sharding for x in jax.tree_util.tree_leaves(runner.params)] == shardings
    for out in (*during, after):
        assert out == before


def test_dp_stream_delivers_every_row_once_in_order_with_acks():
    """A config-built stream under ``mesh: {dp: 2}``: coalesced emissions on
    the dp-scaled grid, every source row written once, in order, and acked."""
    _need_devices(2)
    from arkflow_tpu.config import StreamConfig
    from arkflow_tpu.runtime import build_stream
    from tests.test_runtime import CollectOutput

    rows = [f"dp row {i:02d}" for i in range(22)]  # no multiple of a bucket
    cfg = StreamConfig.from_mapping({
        "name": "dp-e2e",
        "input": {"type": "fault", "redeliver_unacked": True,
                  "inner": {"type": "memory", "messages": rows}},
        "buffer": {"type": "memory", "capacity": 16, "timeout": "10ms",
                   "coalesce": {"batch_buckets": [4, 8], "deadline": "5ms"}},
        "pipeline": {
            "thread_num": 2,
            "processors": [_DP2_PROC],
        },
        "output": {"type": "drop"},
    })
    stream = build_stream(cfg)
    sink = stream.output = CollectOutput()
    runner = stream.pipeline.processors[0].runner
    assert runner.mesh is not None and runner.buckets.batch_buckets == (4, 8)
    asyncio.run(asyncio.wait_for(stream.run(asyncio.Event()), timeout=60))
    written = [r.decode() for b in sink.batches for r in b.to_binary()]
    assert written == rows  # once each (no redelivery: all acked), in order
    assert all("label" in b.schema.names for b in sink.batches)
    assert stream.m_errors.value == 0
    assert stream.input._outstanding == 0  # the broker settled every read


@pytest.mark.parametrize("dp", [2, 4])
def test_health_report_under_a_mesh(dp):
    """``/health`` of a runner on a mesh: the serving core's state, the
    model, and the dp-scaled bucket cap (per-chip cap x dp); nothing of the
    removed stage plan."""
    _need_devices(dp)
    from arkflow_tpu.parallel.mesh import MeshSpec
    from arkflow_tpu.tpu.runner import ModelRunner

    r = ModelRunner("bert_classifier", TINY_BERT,
                    buckets=BucketPolicy((4, 8), (16,)), mesh_spec=MeshSpec(dp=dp))
    r.infer_sync(_tiny_inputs(n=8 * dp))
    rep = r.health_report()
    assert rep["state"] == "healthy" and rep["model"] == "bert_classifier"
    assert rep["bucket_cap"] == 8 * r.mesh.shape["dp"] == 8 * dp
    assert "pp" not in rep and not hasattr(r, "pp_report")
