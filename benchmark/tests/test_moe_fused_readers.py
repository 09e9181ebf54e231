"""``moe_chunks_fused_pct``, ``moe_fused_step_ms`` and
``moe_expert_ms_per_fused_step`` (PR 56) by hand on made-up counters and a
made-up trace, what they read on a program that does not fuse (the parent),
and their entries in ``BENCHMARK.json``.
``python -m pytest benchmark/tests -q``; outside ``tests/``."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = ["kanana2_l6.summarize_backlog"]
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

MS = 1e6                                   # the trace's times are in ns
CHUNKS = "arkflow_gen_chunks_total"
TILE = ("%moe_expert_swiglu.7 = bf16[16,2048]{1,0} custom-call(s32[1]{0} "
        "%reshape.9, bf16[16,2048]{1,0} %slice.3), "
        "custom_call_target=\"tpu_custom_call\"")
WHOLE = ("%moe_expert_grouped.3 = bf16[144,2048]{1,0} custom-call(s32[1]{0} "
         "%reshape.9, bf16[144,2048]{1,0} %fusion.3), "
         "custom_call_target=\"tpu_custom_call\"")
ATTN = ("%mla_paged_attention.5 = bf16[16,1,32,512]{3,2,1,0} custom-call(s32[1]{0} "
        "%reshape.9), custom_call_target=\"tpu_custom_call\"")


def _reader(name):
    from benchmark.run import load_module

    return load_module("metrics", name).read


def _counted(open_, close):
    """A view over two registry snapshots, as ``benchmark/run.py::View``."""
    from benchmark.run import View

    view = View.__new__(View)
    view._open, view._close = open_, close
    return view


def _key(mode):
    return (CHUNKS, (("mode", mode), ("model", "decoder_lm")))


def test_chunks_fused_by_hand():
    """The window gains 300 chunks that rode and 4 that ran alone (the first
    fill): 98.7 %. What was counted before the window does not count; a
    server that alternates (the parent has the counter) reads 0, not
    nothing; a window without chunks nothing."""
    read = _reader("moe_chunks_fused_pct")
    open_ = {_key("fused"): 40.0, _key("alone"): 16.0}
    close = {_key("fused"): 340.0, _key("alone"): 20.0}
    assert read(_counted(open_, close)) == pytest.approx(100 * 300 / 304)
    alternating = {_key("fused"): 0.0, _key("alone"): 500.0}
    assert read(_counted({_key("fused"): 0.0, _key("alone"): 16.0}, alternating)) == 0.0
    assert read(_counted(close, close)) is None
    assert read(_counted({}, {})) is None


def _traced(name, dev, modules=None):
    trace = None if dev is None else {"first_device": dev, "modules": modules or {}}
    return _reader(name)(types.SimpleNamespace(trace=trace))


def test_fused_step_ms_by_hand():
    """The median of the ``jit__fused`` program's executions, whatever else
    ran; nothing where there is no such module (the parent) or no trace."""
    modules = {"jit__fused": [0.0071, 0.0069, 0.0073], "jit__decode": [0.0059],
               "jit__chunk": [0.0052]}
    assert _traced("moe_fused_step_ms", {}, modules) == pytest.approx(7.1)
    parent = {"jit__decode": [0.0059], "jit__chunk": [0.0052]}
    assert _traced("moe_fused_step_ms", {}, parent) is None
    assert _traced("moe_fused_step_ms", None) is None
    read = _reader("moe_fused_step_ms")
    assert read(types.SimpleNamespace(trace={"modules": {}})) is None


def test_expert_ms_per_fused_step_by_hand():
    """Two fused steps and a decode step. Five expert layers' grouped
    products of 0.9 ms in each fused step: 4.5 ms a fused step. The decode
    step's one-tile calls and the attention do not count; a fused step of
    one tile's rows (a smaller chunk) counts its one-tile calls."""
    modules = [["jit__fused(3)", 0.0, 8 * MS], ["jit__decode(1)", 8 * MS, 6 * MS],
               ["jit__fused(3)", 14 * MS, 8 * MS]]
    grouped = {"modules": modules, "ops": [
        *[[WHOLE, (t0 + 1 + 1.2 * i) * MS, 0.9 * MS] for t0 in (0, 14) for i in range(5)],
        [ATTN, 7 * MS, 0.05 * MS], *[[TILE, (8 + i) * MS, 0.8 * MS] for i in range(5)]]}
    read = "moe_expert_ms_per_fused_step"
    assert _traced(read, grouped) == pytest.approx(4.5)
    tiles = {"modules": modules, "ops": [
        [op[0].replace("moe_expert_grouped.3", "moe_expert_swiglu.9"), *op[1:]]
        for op in grouped["ops"]]}
    assert _traced(read, tiles) == pytest.approx(4.5)
    # the trace's short form of the name reads the same
    short = {**grouped, "ops": [[op[0].split(" = ")[0].lstrip("%"), *op[1:]]
                                for op in grouped["ops"]]}
    assert _traced(read, short) == pytest.approx(4.5)


def test_expert_ms_per_fused_step_nothing_to_read():
    """A run without a trace, a trace without a device, a program that does
    not fuse (the parent: decode steps and chunks alone) and a fused step
    without an expert product leave the metric out; none raises."""
    name = "moe_expert_ms_per_fused_step"
    assert _traced(name, None) is None
    read = _reader(name)
    assert read(types.SimpleNamespace(trace={"devices": 0})) is None
    assert read(types.SimpleNamespace()) is None
    assert _traced(name, {"modules": [["jit__decode(1)", 0.0, 6 * MS],
                                      ["jit__chunk(2)", 6 * MS, 5 * MS]],
                          "ops": [[TILE, 1 * MS, 0.8 * MS],
                                  [TILE, 7 * MS, 0.6 * MS]]}) is None
    assert _traced(name, {"modules": [["jit__fused(3)", 0.0, 8 * MS]],
                          "ops": [[ATTN, 1 * MS, 0.05 * MS]]}) is None


@pytest.mark.parametrize("name,unit,better,source,layer", [
    ("moe_chunks_fused_pct", "%", "higher", "program_counter", "scheduler, generate"),
    ("moe_fused_step_ms", "ms", "lower", "device_trace", "device step, generate"),
    ("moe_expert_ms_per_fused_step", "ms", "lower", "device_trace", "kernels")])
def test_their_entries(name, unit, better, source, layer):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better, "source": source,
                     "layer": layer, "moves": "tokens_per_s", "workloads": CELL}
    # appended behind PR 55's entries, in ISSUE 56's order: nothing moved
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index("moe_chunks_fused_pct") == names.index(
        "eva_window_pages_share_of_cache_pct") + 1
    assert names[names.index("moe_chunks_fused_pct"):][:3] == [
        "moe_chunks_fused_pct", "moe_fused_step_ms", "moe_expert_ms_per_fused_step"]
    assert layer in {m["layer"] for m in BENCH["per_layer"][:names.index(name)]}
    assert os.path.exists(os.path.join(ROOT, "benchmark/metrics", name + ".py"))
    (tokens,) = [e for e in BENCH["end_to_end"] if e["name"] == "tokens_per_s"]
    assert set(CELL) <= set(tokens["workloads"])
