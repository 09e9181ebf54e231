"""The readers of the program's own stages (the generation serve loop's
``gen_*`` stages and spans, the classify step's name and its fetch) find
something to read in a traced ``--rehearse`` run of each of their cells. On
the CPU there is no device plane, so no module line: ``classify_step_ms``
must stay out of the line there, never stand in it with a host number."""

import json
import math
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

STAGE_READERS = {
    "gen_host_gap_ms", "gen_prepare_ms", "gen_handoff_ms", "gen_apply_ms",
    "gen_queue_wait_p50_ms", "gen_prefill_p50_ms", "gen_token_gap_ms",
    "classify_step_ms", "device_dispatch_wait_p50_ms", "device_fetch_ms"}
NEEDS_DEVICE_PLANE = {"classify_step_ms"}


def test_every_stage_reader_is_listed_with_a_file():
    listed = {m["name"] for m in BENCH["per_layer"]}
    assert STAGE_READERS <= listed
    for name in STAGE_READERS:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py"))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_rehearsal_reaches_the_stage_readers(cell):
    expected = {m["name"] for m in BENCH["per_layer"]
                if m["name"] in STAGE_READERS and cell in m["workloads"]}
    assert expected, "every cell reports some of them"
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "2147483659", "--seconds", "4", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    for name in expected - NEEDS_DEVICE_PLANE:
        value = line["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0.0, (name, value)
    assert not NEEDS_DEVICE_PLANE & set(line["metrics"])
    if any(name.startswith("gen_") for name in expected):
        # the serve loop's stages sit on the profiler's clock: idle time
        # is named by them
        gaps = {name for name, _ in line["breakdown"]["idle_gaps"]}
        assert any(name.startswith("gen_") for name in gaps), gaps
