"""Start-up under spans (``arkflow_tpu/obs/startup.py``): every phase of a
construction is a ``setup_*`` stage — histogram AND profiler annotation —,
a program's first call a ``setup_cold_step{program}``, JAX's compiles are
heard by the program, and none of it is touched by a warm step. Both
serving paths, tiny models, the CPU."""

from __future__ import annotations

import asyncio
import glob
import json
import os
import threading
import time

import jax
import pytest

from arkflow_tpu.batch import MessageBatch
from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded
from arkflow_tpu.obs import global_registry
from arkflow_tpu.obs.startup import (OTHER, _STATE, cold_step, setup_stage,
                                     startup_report)

ensure_plugins_loaded()

TINY_BERT = {"vocab_size": 128, "hidden": 16, "layers": 1, "heads": 2,
             "ffn": 32, "max_positions": 32, "num_labels": 2}
TINY_DECODER = {"vocab_size": 128, "dim": 32, "layers": 1, "heads": 2,
                "kv_heads": 1, "ffn": 48, "max_seq": 64}
CONFIGS = {
    # the paged kernel, interpreted: the parity probe runs on the CPU too.
    # Lockstep, so that a warm step is warm for JAX as well: one step ahead,
    # a decode step takes the step before's tokens, a stand-in where none is
    # in flight and a step's committed output where one is, and JAX lowers
    # ``_decode`` once for each, whichever serve first meets the other
    "generate": {"type": "tpu_generate", "model": "decoder_lm",
                 "model_config": TINY_DECODER, "serving": "continuous",
                 "max_input": 16, "max_new_tokens": 3, "slots": 2,
                 "page_size": 4, "prefill_chunk": 4, "eos_id": -1,
                 "batch_buckets": [2], "seq_buckets": [16],
                 "decode_kernel": "paged", "kernel_interpret": True,
                 "dispatch_depth": 1},
    "inference": {"type": "tpu_inference", "model": "bert_classifier",
                  "model_config": TINY_BERT, "max_seq": 16,
                  "batch_buckets": [2], "seq_buckets": [16],
                  "outputs": ["label", "score"]},
}
BUILD_STAGES = {
    "generate": {"setup_init_params", "setup_place", "setup_build", "setup_probe"},
    "inference": {"setup_init_params", "setup_place", "setup_build"},
}
PROGRAMS = {"generate": {"_chunk", "_fused"}, "inference": {"classify_step"}}
PATHS = pytest.mark.parametrize("path", sorted(CONFIGS))


def _build(path: str, **over):
    return build_component("processor", {**CONFIGS[path], **over}, Resource())


def _batch() -> MessageBatch:
    return MessageBatch.new_binary([b"a b c d e f g h i", b"j k l"])


def _serve(proc, times: int = 1) -> None:
    async def go():
        for _ in range(times):
            await proc.process(_batch())

    asyncio.run(asyncio.wait_for(go(), timeout=120))


def _setup_series() -> dict:
    """(count, sum) of every series start-up feeds, by name and labels."""
    out = {}
    for m in global_registry().collect():
        labels = tuple(sorted(m.labels.items()))
        if (m.name == "arkflow_stage_seconds"
                and m.labels.get("stage", "").startswith("setup_")) \
                or m.name == "arkflow_jax_compile_seconds":
            out[m.name, labels] = (m.count, m.sum)
        elif m.name in ("arkflow_setup_cold_seconds_total",
                        "arkflow_jax_compile_cache_total"):
            out[m.name, labels] = (m.value, m.value)
    return out


@pytest.fixture(autouse=True)
def quiet_process():
    """The series are the process's: wait out what an earlier test file of
    this worker left compiling on a background thread (a tuner's warm, a
    monitor's probe), so that what a test counts is its own."""
    last, deadline = _setup_series(), time.monotonic() + 15.0
    while time.monotonic() < deadline:
        time.sleep(0.2)
        now = _setup_series()
        if now == last:
            return
        last = now


def _stages_since(before: dict) -> dict:
    """stage -> (observations, seconds) gained since ``before``."""
    gained: dict = {}
    for (name, labels), (count, total) in _setup_series().items():
        c0, s0 = before.get((name, labels), (0, 0.0))
        if name == "arkflow_stage_seconds" and count > c0:
            stage = dict(labels)["stage"]
            c, s = gained.get(stage, (0, 0.0))
            gained[stage] = (c + count - c0, s + total - s0)
    return gained


# -- the helper by itself -------------------------------------------------------

def test_a_setup_stage_observes_its_self_time():
    """What a nested setup stage took is its own: the outer stage observes
    its duration LESS it, so a construction's stages add up to its wall."""
    before = _setup_series()
    t0 = time.perf_counter()
    with setup_stage("setup_build"):
        time.sleep(0.02)
        with setup_stage("setup_place"):
            time.sleep(0.05)
    wall = time.perf_counter() - t0
    got = _stages_since(before)
    assert got["setup_build"][0] == got["setup_place"][0] == 1
    assert 0.05 <= got["setup_place"][1] < wall
    assert 0.02 <= got["setup_build"][1] < wall - 0.05 + 1e-3
    assert got["setup_build"][1] + got["setup_place"][1] <= wall


def test_overlapping_cold_steps_count_their_wall_time_once():
    """Two workers meet a first-seen shape at once: the histogram counts
    both, the counter the wall time with at least one in flight."""
    before = _setup_series()
    barrier = threading.Barrier(2)

    def first_call():
        with cold_step("no-such-program"):
            barrier.wait(timeout=10)
            time.sleep(0.05)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=first_call) for _ in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=10)
        assert not th.is_alive()
    wall = time.perf_counter() - t0
    after = _setup_series()
    key = ("arkflow_setup_cold_seconds_total", ())
    counted = after[key][0] - before.get(key, (0.0, 0.0))[0]
    steps = _stages_since(before)["setup_cold_step"]
    assert steps[0] == 2 and steps[1] >= 0.1
    assert 0.05 <= counted <= wall < steps[1]
    # a name no runner or server built makes no label of its own
    labels = {dict(l).get("program") for (n, l) in after
              if n == "arkflow_stage_seconds"}
    assert OTHER in labels and "no-such-program" not in labels


# -- a construction, both paths ---------------------------------------------------

@PATHS
def test_a_build_observes_every_setup_stage_once_within_its_wall_time(path):
    before = _setup_series()
    t0 = time.perf_counter()
    _build(path)
    wall = time.perf_counter() - t0
    got = _stages_since(before)
    assert set(got) == BUILD_STAGES[path], got
    assert all(count == 1 for count, _ in got.values()), got
    assert 0.0 < sum(s for _, s in got.values()) <= wall


@PATHS
def test_setup_stages_are_profiler_annotations_of_the_same_name(path, tmp_path):
    """Start-up shares the device trace's clock, as the loop's stages do."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # the annotations are TraceMe events
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        proc = _build(path)
        _serve(proc)
    finally:
        jax.profiler.stop_trace()
    trace = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    names = {e.name for pl in ProfileData.from_file(trace).planes
             for ln in pl.lines for e in ln.events}
    cold = {f"setup_cold_step:{program}" for program in PROGRAMS[path]}
    assert BUILD_STAGES[path] | cold <= names, sorted(
        n for n in names if n.startswith("setup_"))


@PATHS
def test_a_restore_observes_setup_restore(path, tmp_path):
    from arkflow_tpu.tpu import checkpoint

    first = _build(path)
    host = getattr(first, "host_params", None)
    if host is None:
        host = first.runner.host_params
    ckpt = str(tmp_path / "ckpt")
    checkpoint.save(ckpt, host)
    before = _setup_series()
    _build(path, checkpoint=ckpt)
    got = _stages_since(before)
    assert got["setup_restore"][0] == 1
    assert set(got) == BUILD_STAGES[path] | {"setup_restore"}


@PATHS
def test_a_first_call_is_a_cold_step_and_a_second_is_not(path):
    proc = _build(path)
    assert set(startup_report()["cold_programs"]) >= PROGRAMS[path]
    before = _setup_series()
    _serve(proc)
    first = _setup_series()
    for program in PROGRAMS[path]:
        cold = ("arkflow_stage_seconds",
                (("program", program), ("stage", "setup_cold_step")))
        assert first[cold][0] > before.get(cold, (0, 0.0))[0], program
        for phase in ("trace", "lower", "backend_compile"):
            heard = ("arkflow_jax_compile_seconds",
                     (("phase", phase), ("program", program)))
            assert first[heard][0] > before.get(heard, (0, 0.0))[0], heard
    wall = ("arkflow_setup_cold_seconds_total", ())
    assert first[wall][0] > before.get(wall, (0.0, 0.0))[0]
    assert not set(startup_report()["cold_programs"]) & PROGRAMS[path]
    _serve(proc)
    assert _setup_series() == first


@PATHS
def test_fifty_warm_steps_touch_no_setup_or_compile_series(path):
    proc = _build(path)
    _serve(proc)
    warm = _setup_series()
    _serve(proc, times=50 if path == "inference" else 10)  # 10 x (1 + 3 + …) steps
    assert _setup_series() == warm
    programs = {dict(labels)["program"] for (name, labels) in warm
                if "program" in dict(labels)}
    assert programs <= {"_decode", "_chunk", "_prefill", "_fused", "_verify",
                        "classify_step", OTHER}


def test_the_listeners_register_once_however_many_are_built():
    from jax._src import monitoring

    for path in sorted(CONFIGS):
        _build(path)
    for listeners, ours in (
            (monitoring.get_event_duration_listeners(), _STATE._on_duration),
            (monitoring.get_event_listeners(), _STATE._on_event),
            (monitoring.get_scalar_listeners(), _STATE._on_scalar)):
        assert sum(1 for fn in listeners if fn == ours) == 1
    starts = [m for m in global_registry().collect()
              if m.name == "arkflow_process_start_time_seconds"]
    assert len(starts) == 1
    assert 0.0 < time.time() - starts[0].value < 24 * 3600


def test_health_carries_startup():
    import aiohttp

    from arkflow_tpu.config import EngineConfig
    from arkflow_tpu.runtime.engine import Engine

    port = 18979

    async def go():
        cfg = EngineConfig.from_mapping({
            "health_check": {"host": "127.0.0.1", "port": port},
            "streams": [{
                "name": "classified",
                "input": {"type": "generate", "payload": "a b c",
                          "interval": "20ms", "batch_size": 2},
                "pipeline": {"thread_num": 1,
                             "processors": [CONFIGS["inference"]]},
                "output": {"type": "drop"},
            }],
        })
        engine = Engine(cfg)
        task = asyncio.create_task(engine.run())
        try:
            for _ in range(200):
                await asyncio.sleep(0.05)
                if engine._ready and "classify_step" not in \
                        startup_report()["cold_programs"]:
                    break
            async with aiohttp.ClientSession() as s:
                async with s.get(f"http://127.0.0.1:{port}/health") as r:
                    return json.loads(await r.text())
        finally:
            engine.shutdown()
            try:
                await asyncio.wait_for(task, timeout=10)
            except (asyncio.TimeoutError, Exception):
                task.cancel()

    startup = asyncio.run(asyncio.wait_for(go(), timeout=60))["startup"]
    assert startup["process_start_time_seconds"] > 0
    assert BUILD_STAGES["inference"] | {"setup_cold_step"} <= set(
        startup["stage_seconds"])
    assert startup["cold_step_seconds"]["classify_step"] > 0
    assert set(startup["compile_seconds"]) >= {"trace", "lower", "backend_compile"}
    assert set(startup["compile_cache"]) == {"hits", "misses"}
    assert "classify_step" not in startup["cold_programs"]
