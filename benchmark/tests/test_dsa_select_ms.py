"""``dsa_select_ms_per_step`` (PR 47) by hand on a made-up trace, what it
reads where the kernel never ran, and its entry in ``BENCHMARK.json``.
``python -m pytest benchmark/tests -q``; outside ``tests/``."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
NAME = "dsa_select_ms_per_step"
CELLS = ["dots3_l5.summarize_long_backlog"]
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

MS = 1e6                                   # the trace's times are in ns
SELECT = ("%dsa_topk_select.3 = f32[32,12544]{1,0} custom-call(s32[32,1]{1,0} "
          "%bitcast.61, f32[32,12544]{1,0} %bitcast.62), "
          "custom_call_target=\"tpu_custom_call\"")
# the latent kernel takes the mask as an operand: its line names the kernel too
USER = ("%dsa_sparse_attention.5 = bf16[32,128,512]{2,1,0} custom-call(s32[1]{0} "
        "%reshape.9, f32[32,1,12544]{2,1,0} %dsa_topk_select.3), "
        "custom_call_target=\"tpu_custom_call\"")
SORT = ("%sort.27 = (f32[32,8]{1,0}, s32[32,8]{1,0}) sort(f32[32,256]{1,0} "
        "%fusion.1, s32[32,256]{1,0} %iota.44), dimensions={1}, is_stable=true")


def _read(dev):
    from benchmark.run import load_module

    trace = None if dev is None else {"first_device": dev}
    return load_module("metrics", NAME).read(types.SimpleNamespace(trace=trace))


def test_by_hand():
    """Two indexed layers a step: the kernel's calls of 0.05 and 0.04 ms inside
    each of two decode executions count, 0.09 ms a step; the latent kernel
    that consumes the mask, the router's sort and a chunk's eight calls of the
    same kernel do not."""
    dev = {"modules": [["jit__decode(1)", 0.0, 12 * MS],
                       ["jit__chunk(2)", 12 * MS, 40 * MS],
                       ["jit__decode(1)", 52 * MS, 12 * MS]],
           "ops": [[SELECT, 1 * MS, 0.05 * MS], [SELECT, 5 * MS, 0.04 * MS],
                   [USER, 6 * MS, 0.7 * MS], [SORT, 8 * MS, 0.02 * MS],
                   *[[SELECT.replace("32,", "64,"), (13 + i) * MS, 0.075 * MS]
                     for i in range(8)],
                   [SELECT, 53 * MS, 0.05 * MS], [SELECT, 57 * MS, 0.04 * MS],
                   [USER, 58 * MS, 0.7 * MS]]}
    assert _read(dev) == pytest.approx(0.09)
    # the trace's short form of the name reads the same
    short = {**dev, "ops": [[op[0].split(" = ")[0].lstrip("%"), *op[1:]]
                            for op in dev["ops"]]}
    assert _read(short) == pytest.approx(0.09)


def test_nothing_to_read():
    """A run without a trace, a trace without a device, a program that still
    sorts (the parent of PR 47: its decode step holds no such kernel) and a
    trace without a decode step leave the metric out; none raises."""
    assert _read(None) is None
    from benchmark.run import load_module

    read = load_module("metrics", NAME).read
    assert read(types.SimpleNamespace(trace={"devices": 0})) is None
    parent = {"modules": [["jit__decode(1)", 0.0, 17 * MS]],
              "ops": [[SORT.replace("32,256", "32,1,12544"), 1 * MS, 2.7 * MS],
                      [USER.replace("%dsa_topk_select.3", "%fusion.77"), 6 * MS, 0.7 * MS]]}
    assert _read(parent) is None
    assert _read({"modules": [["jit__chunk(2)", 0.0, 40 * MS]],
                  "ops": [[SELECT, 1 * MS, 0.075 * MS]]}) is None


def test_its_entry():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "kernels",
                     "moves": "tokens_per_s", "workloads": CELLS}
    # appended behind PR 46's entries: nothing that was there moved
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NAME) == names.index("conv_moe_expert_hbm_pct") + 1
    assert os.path.exists(os.path.join(ROOT, "benchmark/metrics", NAME + ".py"))
    ends = {e["name"] for e in BENCH["end_to_end"]
            if "workloads" not in e or set(CELLS) <= set(e["workloads"])}
    assert CELLS[0] in {w["name"] for w in BENCH["workloads"]} and "tokens_per_s" in ends
    # the sorts' reader stays as it was, beside it, on the same cell
    (sorts,) = [m for m in BENCH["per_layer"] if m["name"] == "dsa_topk_ms_per_step"]
    assert sorts["workloads"] == CELLS
