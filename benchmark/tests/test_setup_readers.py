"""The nine readers of start-up (``setup_init_params_s``, ``setup_place_s``,
``setup_build_s``, ``setup_probe_s``, ``setup_cold_steps_s``,
``setup_compile_s``, ``setup_compile_cache_hit_pct``, ``setup_unnamed_s``,
``backend_compiles_in_window``): their arithmetic on a made-up snapshot of
the registry at the window's open, nothing on a snapshot without the
program's series (the parent), their entries in ``BENCHMARK.json``, and all
nine on a traced ``--rehearse`` line of one classify and one generate cell."""

import importlib.util
import json
import math
import os
import subprocess
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

READERS = {
    "setup_init_params_s": ("s", "lower", "program_span"),
    "setup_place_s": ("s", "lower", "program_span"),
    "setup_build_s": ("s", "lower", "program_span"),
    "setup_probe_s": ("s", "lower", "program_span"),
    "setup_cold_steps_s": ("s", "lower", "program_counter"),
    "setup_compile_s": ("s", "lower", "program_counter"),
    "setup_compile_cache_hit_pct": ("%", "higher", "program_counter"),
    "setup_unnamed_s": ("s", "lower", "program_span"),
    "backend_compiles_in_window": ("count", "lower", "program_counter"),
}
CELLS = [w["name"] for w in BENCH["workloads"]]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def key(name, **labels):
    return name, tuple(sorted(labels.items()))


STAGES, COMPILE = "arkflow_stage_seconds", "arkflow_jax_compile_seconds"
CACHE = "arkflow_jax_compile_cache_total"


def snapshot(started_ago_s: float) -> dict:
    """A registry as ``run.py::registry_snapshot`` keeps it: histograms as
    (sum, count), the rest by value. The process started
    ``started_ago_s`` before now."""
    return {
        key("arkflow_process_start_time_seconds"): time.time() - started_ago_s,
        key(STAGES, stage="setup_init_params"): (40.0, 1),
        key(STAGES, stage="setup_restore"): (2.0, 1),
        key(STAGES, stage="setup_place"): (0.75, 1),
        key(STAGES, stage="setup_build"): (3.0, 1),
        key(STAGES, stage="setup_cold_step", program="_chunk"): (9.0, 2),
        key(STAGES, stage="setup_cold_step", program="_decode"): (6.0, 1),
        key(STAGES, stage="gen_prepare"): (5.0, 999),
        key("arkflow_setup_cold_seconds_total"): 14.0,  # two overlapped
        key(COMPILE, phase="trace", program="_decode"): (1.0, 1),
        key(COMPILE, phase="lower", program="_decode"): (2.0, 1),
        key(COMPILE, phase="backend_compile", program="_decode"): (4.0, 1),
        key(COMPILE, phase="backend_compile", program="other"): (0.5, 40),
        key(COMPILE, phase="cache_retrieval", program="other"): (3.5, 41),
        key(CACHE, result="hit"): 30.0,
        key(CACHE, result="miss"): 10.0,
    }


class MadeUpView:
    """What the readers look at: the two snapshots, ``hist`` as
    ``run.py::View.hist`` computes it, and the run's window stamp."""

    def __init__(self, snap_open, snap_close=None, opened_ago_s=0.0):
        self._open, self._close = snap_open, snap_close or snap_open
        self.run = types.SimpleNamespace(
            t_open=time.perf_counter() - opened_ago_s)

    def hist(self, name, **labels):
        def total(snap):
            got = [v for (n, lab), v in snap.items() if n == name and all(
                dict(lab).get(k) == val for k, val in labels.items())]
            return sum(s for s, _ in got), sum(c for _, c in got)

        (s0, c0), (s1, c1) = total(self._open), total(self._close)
        return s1 - s0, c1 - c0


def test_arithmetic_of_each_reader():
    # the process started 100 s before now, the window opened 20 s before now
    snap = snapshot(started_ago_s=100.0)
    close = dict(snap)
    close[key(COMPILE, phase="backend_compile", program="_decode")] = (9.0, 3)
    view = MadeUpView(snap, close, opened_ago_s=20.0)
    assert reader("setup_init_params_s")(view) == pytest.approx(42.0)
    assert reader("setup_place_s")(view) == pytest.approx(0.75)
    assert reader("setup_build_s")(view) == pytest.approx(3.0)
    # a stage never observed reads 0, not nothing
    assert reader("setup_probe_s")(view) == 0.0
    # the counter, not the histogram's 15.0
    assert reader("setup_cold_steps_s")(view) == pytest.approx(14.0)
    # trace + lower + backend_compile over programs; never cache_retrieval
    assert reader("setup_compile_s")(view) == pytest.approx(7.5)
    assert reader("setup_compile_cache_hit_pct")(view) == pytest.approx(75.0)
    named = 42.0 + 0.75 + 3.0 + 0.0 + 14.0
    assert reader("setup_unnamed_s")(view) == pytest.approx(
        80.0 - named, abs=0.05)
    assert reader("backend_compiles_in_window")(view) == 2.0


@pytest.mark.parametrize("name", sorted(READERS))
def test_nothing_on_a_program_without_the_series(name):
    """The parent: no process-start gauge in the snapshot, whatever else is
    there; and a run whose snapshot at the open was never taken."""
    bare = {k: v for k, v in snapshot(100.0).items()
            if k[0] in (STAGES,) and dict(k[1])["stage"] == "gen_prepare"}
    assert reader(name)(MadeUpView(bare)) is None
    assert reader(name)(MadeUpView({})) is None


def test_no_lookup_no_hit_rate():
    snap = {k: v for k, v in snapshot(100.0).items() if k[0] != CACHE}
    assert reader("setup_compile_cache_hit_pct")(MadeUpView(snap)) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_entry_in_benchmark_json(name):
    entries = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    unit, better, source = READERS[name]
    assert entries[0] == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": "warm-up", "moves": "setup_s", "workloads": CELLS}
    assert os.path.isfile(os.path.join(
        ROOT, "benchmark", "metrics", name + ".py"))


def test_entries_are_appended_at_the_end():
    assert [m["name"] for m in BENCH["per_layer"][-len(READERS):]] == list(READERS)


@pytest.mark.parametrize("cell", ["bert_base.classify_backlog",
                                  "mistral_l6.summarize_backlog"])
def test_all_nine_on_a_rehearsed_line(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
         "2147483951", "--seconds", "4", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = {name: line["metrics"][name]["value"] for name in READERS}
    assert all(math.isfinite(v) and v >= 0.0 for v in got.values()), got
    assert got["backend_compiles_in_window"] == 0.0
    assert got["setup_init_params_s"] > 0 and got["setup_cold_steps_s"] > 0
    # the six terms close set-up: the run's setup_s, whose clock starts at
    # the harness's first line, plus the interpreter's own start before it
    terms = sum(got[n] for n in (
        "setup_init_params_s", "setup_place_s", "setup_build_s",
        "setup_probe_s", "setup_cold_steps_s", "setup_unnamed_s"))
    assert 0.0 <= terms - line["detail"]["setup_s"] < 0.5
