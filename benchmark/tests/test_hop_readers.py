"""The five readers of the generate step's executor hop (``gen_dispatch_ms``,
``gen_ready_wait_ms``, ``gen_fetch_ms``, ``gen_launch_wake_ms``,
``gen_hop_unnamed_ms``): their arithmetic on a hand-made ``View``, their
entries in ``BENCHMARK.json``, and that a traced ``--rehearse`` run finds the
program's stages. On the CPU there is no device plane, so no module line:
``gen_launch_wake_ms`` must stay out of the line there."""

import importlib.util
import json
import math
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

HOST_ONLY = {"gen_dispatch_ms", "gen_ready_wait_ms", "gen_fetch_ms",
             "gen_hop_unnamed_ms"}
HOP_READERS = HOST_ONLY | {"gen_launch_wake_ms"}
GENERATE_CELLS = {w["name"] for w in BENCH["workloads"]
                  if w["name"] != "bert_base.classify_backlog"}
STATIONARY_CELLS = {"mistral_l6.summarize_backlog",
                    "mistral_tp4.summarize_backlog",
                    "kanana2_l6.summarize_backlog"}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class HandMadeView:
    """``View.hist`` over fixed (seconds, observations) by label set, label
    subsets matched as ``run.py::matching`` matches them, and a ``trace``."""

    def __init__(self, hists, modules=None):
        self._hists = hists
        self.trace = None if modules is None else {"modules": modules}

    def hist(self, name, **labels):
        assert name == "arkflow_stage_seconds"
        got = [v for lab, v in self._hists.items()
               if all(dict(lab).get(k) == val for k, val in labels.items())]
        return sum(s for s, _ in got), sum(c for _, c in got)


def lab(stage, kind=None):
    return (("stage", stage),) + ((("kind", kind),) if kind else ())


#: 100 decode steps and 50 chunks, 20 of which left their token on the device
HISTS = {
    lab("gen_device_wait"): (1.300, 150),
    lab("gen_handoff"): (0.045, 150),
    lab("gen_dispatch", "decode"): (0.030, 100),
    lab("gen_dispatch", "chunk"): (0.030, 50),
    lab("gen_ready_wait", "decode"): (0.800, 100),
    lab("gen_ready_wait", "chunk"): (0.400, 50),
    lab("gen_fetch", "decode"): (0.020, 100),
    lab("gen_fetch", "chunk"): (0.006, 30),
}
MODULES = {"jit__decode": [0.0070] * 60 + [0.0073] * 40,
           "jit__chunk": [0.0120] * 50}


def test_arithmetic_of_each_reader():
    view = HandMadeView(HISTS, MODULES)
    assert reader("gen_dispatch_ms")(view) == pytest.approx(0.060 / 150 * 1e3)
    assert reader("gen_ready_wait_ms")(view) == pytest.approx(8.0)
    # its own observations: 130 steps fetched, not 150
    assert reader("gen_fetch_ms")(view) == pytest.approx(0.026 / 130 * 1e3)
    # mean wait for a decode step less the MEAN decode execution (7.12 ms)
    assert reader("gen_launch_wake_ms")(view) == pytest.approx(8.0 - 7.12)
    assert reader("gen_hop_unnamed_ms")(view) == pytest.approx(
        (1.300 - 0.060 - 1.200 - 0.026) / 150 * 1e3)


def test_launch_wake_needs_a_module_line_and_the_same_steps():
    read = reader("gen_launch_wake_ms")
    assert read(HandMadeView(HISTS)) is None
    assert read(HandMadeView(HISTS, {})) is None
    assert read(HandMadeView(HISTS, {"jit__chunk": [0.012]})) is None
    # a trace of longer steps than the window's mean wait: not the same steps
    assert read(HandMadeView(HISTS, {"jit__decode": [0.0081]})) is None
    assert read(HandMadeView(HISTS, {"jit__decode": [0.00803]})) \
        == pytest.approx(-0.03)


def test_a_program_without_the_stages_gives_nothing_to_read():
    older = HandMadeView({k: v for k, v in HISTS.items() if len(k) == 1},
                         MODULES)
    for name in HOP_READERS:
        assert reader(name)(older) is None, name


def test_every_hop_reader_is_listed_with_a_file():
    listed = {m["name"]: m for m in BENCH["per_layer"]}
    for name in HOP_READERS:
        entry = listed[name]
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py"))
        assert entry["layer"] == "scheduler, generate"
        assert entry["moves"] == "tokens_per_s" and entry["better"] == "lower"
        assert set(entry["workloads"]) == (
            STATIONARY_CELLS if name == "gen_launch_wake_ms"
            else GENERATE_CELLS)


def test_rehearsal_reaches_the_hop_readers():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mistral_l6.summarize_backlog", "--seed", "2147483693", "--seconds",
         "4", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    for name in HOST_ONLY:
        value = line["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0.0, (name, value)
    assert "gen_launch_wake_ms" not in line["metrics"]
    # the hop's stages sit on the profiler's clock: idle time inside the hop
    # is named by them, not by the hop
    gaps = {name for name, _ in line["breakdown"]["idle_gaps"]}
    assert any(name.startswith("gen_ready_wait:") for name in gaps), gaps
