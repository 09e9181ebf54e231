"""``moe_grouped_chunks_pct`` and ``moe_expert_ms_per_chunk`` (PR 52) by hand
on made-up counters and a made-up trace, what they read on a program that
has neither, and their entries in ``BENCHMARK.json``.
``python -m pytest benchmark/tests -q``; outside ``tests/``."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
ROUTED = ["kanana2_l6.summarize_backlog", "dots3_l5.summarize_long_backlog",
          "kexaone_l5.mixed_backlog", "mimo_l7.long_reason_backlog",
          "lfm2_l12.draft_backlog", "qwen3next_l8.report_backlog"]
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

MS = 1e6                                   # the trace's times are in ns
GROUPED = "arkflow_gen_moe_grouped_products_total"
HIT = "arkflow_gen_moe_experts_hit"
TILE = ("%moe_expert_swiglu.48 = bf16[128,6144]{1,0} custom-call(s32[1]{0} "
        "%reshape.9, bf16[128,6144]{1,0} %slice.3), "
        "custom_call_target=\"tpu_custom_call\"")
WHOLE = ("%moe_expert_grouped.7 = bf16[512,6144]{1,0} custom-call(s32[1]{0} "
         "%reshape.9, bf16[512,6144]{1,0} %fusion.3), "
         "custom_call_target=\"tpu_custom_call\"")
ATTN = ("%paged_flash_attention.5 = bf16[512,64,128]{2,1,0} custom-call(s32[1]{0} "
        "%reshape.9), custom_call_target=\"tpu_custom_call\"")


def _reader(name):
    from benchmark.run import load_module

    return load_module("metrics", name).read


def _counted(open_, close, sizes):
    """A view over two registry snapshots, as ``benchmark/run.py::View``."""
    from benchmark.run import View

    view = View.__new__(View)
    view._open, view._close, view.sizes = open_, close, sizes
    return view


def _key(name, kind):
    return (name, (("kind", kind), ("model", "decoder_lm")))


def test_grouped_chunks_by_hand():
    """Four expert layers; the window gains 30 chunks and 120 grouped
    products among them (every layer of every chunk): 100 %. The decode
    steps' series and what was counted before the window do not count. The
    same chunks on a one-tile chunk gain no product: 0, not nothing."""
    sizes = {"num_hidden_layers": 5, "first_k_dense_replace": 1}
    open_ = {_key(GROUPED, "chunk"): 40.0, _key(GROUPED, "decode"): 0.0,
             _key(HIT, "chunk"): (170.0, 10.0), _key(HIT, "decode"): (900.0, 60.0)}
    close = {_key(GROUPED, "chunk"): 160.0, _key(GROUPED, "decode"): 0.0,
             _key(HIT, "chunk"): (680.0, 40.0), _key(HIT, "decode"): (9000.0, 600.0)}
    read = _reader("moe_grouped_chunks_pct")
    assert read(_counted(open_, close, sizes)) == pytest.approx(100.0)
    flat = {**close, _key(GROUPED, "chunk"): 40.0}
    assert read(_counted(open_, flat, sizes)) == 0.0
    half = {**close, _key(GROUPED, "chunk"): 100.0}
    assert read(_counted(open_, half, sizes)) == pytest.approx(50.0)


def test_grouped_chunks_nothing_to_read():
    """A program that predates the counter (the parent of PR 52), a model
    that routes nothing, a window without chunks: nothing, and no raise."""
    sizes = {"num_hidden_layers": 5, "first_k_dense_replace": 1}
    read = _reader("moe_grouped_chunks_pct")
    hits = {_key(HIT, "chunk"): (680.0, 40.0)}
    assert read(_counted({}, hits, sizes)) is None
    assert read(_counted({}, {}, {"num_hidden_layers": 6})) is None
    assert read(types.SimpleNamespace(sizes=sizes)) is None
    idle = {_key(GROUPED, "chunk"): 0.0, _key(HIT, "chunk"): (0.0, 0.0)}
    assert read(_counted(idle, idle, sizes)) is None


def _traced(dev):
    trace = None if dev is None else {"first_device": dev}
    return _reader("moe_expert_ms_per_chunk")(types.SimpleNamespace(trace=trace))


def test_expert_ms_per_chunk_by_hand():
    """Two chunks. Tile by tile (the parent): four calls a layer of 0.8 ms,
    two layers, 6.4 ms a chunk; grouped: one call a layer of 1.5 ms, 3.0 ms
    a chunk. A decode step's calls and the chunk's attention do not count."""
    modules = [["jit__chunk(2)", 0.0, 30 * MS], ["jit__decode(1)", 30 * MS, 12 * MS],
               ["jit__chunk(2)", 42 * MS, 30 * MS]]
    tiles = {"modules": modules, "ops": [
        *[[TILE, (t0 + 1 + i) * MS, 0.8 * MS] for t0 in (0, 42) for i in range(8)],
        [ATTN, 10 * MS, 3 * MS], [TILE.replace("128,", "48,"), 31 * MS, 1.1 * MS]]}
    assert _traced(tiles) == pytest.approx(6.4)
    grouped = {"modules": modules, "ops": [
        *[[WHOLE, (t0 + 1 + 2 * i) * MS, 1.5 * MS] for t0 in (0, 42) for i in range(2)],
        [ATTN, 10 * MS, 3 * MS], [TILE.replace("128,", "48,"), 31 * MS, 1.1 * MS]]}
    assert _traced(grouped) == pytest.approx(3.0)
    # the trace's short form of the name reads the same
    short = {**grouped, "ops": [[op[0].split(" = ")[0].lstrip("%"), *op[1:]]
                                for op in grouped["ops"]]}
    assert _traced(short) == pytest.approx(3.0)


def test_expert_ms_per_chunk_nothing_to_read():
    """A run without a trace, a trace without a device, a dense model's
    chunk and a trace without a chunk leave the metric out; none raises."""
    assert _traced(None) is None
    read = _reader("moe_expert_ms_per_chunk")
    assert read(types.SimpleNamespace(trace={"devices": 0})) is None
    assert _traced({"modules": [["jit__chunk(2)", 0.0, 30 * MS]],
                    "ops": [[ATTN, 10 * MS, 3 * MS]]}) is None
    assert _traced({"modules": [["jit__decode(1)", 0.0, 12 * MS]],
                    "ops": [[TILE, 1 * MS, 1.1 * MS]]}) is None


@pytest.mark.parametrize("name,unit,better,source", [
    ("moe_grouped_chunks_pct", "%", "higher", "program_counter"),
    ("moe_expert_ms_per_chunk", "ms", "lower", "device_trace")])
def test_their_entries(name, unit, better, source):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better, "source": source,
                     "layer": "kernels", "moves": "tokens_per_s", "workloads": ROUTED}
    # appended behind PR 51's entries: nothing that was there moved
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index("moe_grouped_chunks_pct") == names.index("backend_compiles_in_window") + 1
    assert names[-2:] == ["moe_grouped_chunks_pct", "moe_expert_ms_per_chunk"]
    assert os.path.exists(os.path.join(ROOT, "benchmark/metrics", name + ".py"))
    (tokens,) = [e for e in BENCH["end_to_end"] if e["name"] == "tokens_per_s"]
    assert set(ROUTED) <= set(tokens["workloads"])
