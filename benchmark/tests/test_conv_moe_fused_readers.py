"""``conv_moe_chunks_fused_pct``, ``conv_moe_fused_step_ms``,
``conv_moe_expert_ms_per_fused_step`` and ``conv_moe_fused_hbm_pct`` (PR 58)
by hand on made-up counters and a made-up trace, what they read on a program
that does not fuse (the parent), and their entries in ``BENCHMARK.json``.
``python -m pytest benchmark/tests -q``; outside ``tests/``."""

import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CELL = ["lfm2_l12.draft_backlog"]
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(ROOT, "benchmark/configs/lfm2-8b-a1b-l12.json")) as f:
    SIZES = json.load(f)

MS = 1e6                                   # the trace's times are in ns
CHUNKS = "arkflow_gen_chunks_total"
HIT = "arkflow_gen_moe_experts_hit"
TILE = ("%moe_expert_swiglu.7 = bf16[128,2048]{1,0} custom-call(s32[1]{0} "
        "%reshape.9, bf16[128,2048]{1,0} %slice.3), "
        "custom_call_target=\"tpu_custom_call\"")
WHOLE = ("%moe_expert_grouped.3 = bf16[384,2048]{1,0} custom-call(s32[1]{0} "
         "%reshape.9, bf16[384,2048]{1,0} %fusion.3), "
         "custom_call_target=\"tpu_custom_call\"")
ATTN = ("%paged_flash_attention.5 = bf16[128,1,32,64]{3,2,1,0} custom-call(s32[1]{0} "
        "%reshape.9), custom_call_target=\"tpu_custom_call\"")


def _reader(name):
    from benchmark.run import load_module

    return load_module("metrics", name)


def _counted(open_, close):
    """A view over two registry snapshots, as ``benchmark/run.py::View``."""
    from benchmark.run import View

    view = View.__new__(View)
    view._open, view._close = open_, close
    return view


def _key(mode):
    return (CHUNKS, (("mode", mode), ("model", "decoder_lm")))


def test_chunks_fused_by_hand():
    """The window gains 330 chunks that rode and 6 that ran alone (a fill
    with no lane decoding): 98.2 %. What was counted before the window does
    not count; a server that alternates (the parent has the counter) reads
    0, not nothing; a window without chunks nothing."""
    read = _reader("conv_moe_chunks_fused_pct").read
    open_ = {_key("fused"): 40.0, _key("alone"): 16.0}
    close = {_key("fused"): 370.0, _key("alone"): 22.0}
    assert read(_counted(open_, close)) == pytest.approx(100 * 330 / 336)
    alternating = {_key("fused"): 0.0, _key("alone"): 500.0}
    assert read(_counted({_key("fused"): 0.0, _key("alone"): 16.0}, alternating)) == 0.0
    assert read(_counted(close, close)) is None
    assert read(_counted({}, {})) is None


def _traced(name, dev, modules=None):
    trace = None if dev is None else {"first_device": dev, "modules": modules or {}}
    return _reader(name).read(types.SimpleNamespace(trace=trace))


def test_fused_step_ms_by_hand():
    """The median of the ``jit__fused`` program's executions, whatever else
    ran; nothing where there is no such module (the parent) or no trace."""
    modules = {"jit__fused": [0.0151, 0.0149, 0.0153], "jit__decode": [0.012],
               "jit__chunk": [0.0115]}
    assert _traced("conv_moe_fused_step_ms", {}, modules) == pytest.approx(15.1)
    parent = {"jit__decode": [0.012], "jit__chunk": [0.0115]}
    assert _traced("conv_moe_fused_step_ms", {}, parent) is None
    assert _traced("conv_moe_fused_step_ms", None) is None
    read = _reader("conv_moe_fused_step_ms").read
    assert read(types.SimpleNamespace(trace={"modules": {}})) is None


def test_expert_ms_per_fused_step_by_hand():
    """Two fused steps and a decode step. Ten expert layers' grouped products
    of 0.95 ms in each fused step: 9.5 ms a fused step. The decode step's
    one-tile calls and the attention do not count."""
    modules = [["jit__fused(3)", 0.0, 15 * MS], ["jit__decode(1)", 15 * MS, 12 * MS],
               ["jit__fused(3)", 27 * MS, 15 * MS]]
    grouped = {"modules": modules, "ops": [
        *[[WHOLE, (t0 + 1 + 1.2 * i) * MS, 0.95 * MS] for t0 in (0, 27) for i in range(10)],
        [ATTN, 14 * MS, 0.5 * MS], *[[TILE, (15 + i) * MS, 0.88 * MS] for i in range(10)]]}
    read = "conv_moe_expert_ms_per_fused_step"
    assert _traced(read, grouped) == pytest.approx(9.5)
    # the trace's short form of the name reads the same
    short = {**grouped, "ops": [[op[0].split(" = ")[0].lstrip("%"), *op[1:]]
                                for op in grouped["ops"]]}
    assert _traced(read, short) == pytest.approx(9.5)


def test_expert_ms_per_fused_step_nothing_to_read():
    """A run without a trace, a trace without a device, a program that does
    not fuse (the parent: decode steps and chunks alone) and a fused step
    without an expert product leave the metric out; none raises."""
    name = "conv_moe_expert_ms_per_fused_step"
    assert _traced(name, None) is None
    read = _reader(name).read
    assert read(types.SimpleNamespace(trace={"devices": 0})) is None
    assert read(types.SimpleNamespace()) is None
    assert _traced(name, {"modules": [["jit__decode(1)", 0.0, 12 * MS],
                                      ["jit__chunk(2)", 12 * MS, 11 * MS]],
                          "ops": [[TILE, 1 * MS, 0.88 * MS],
                                  [WHOLE, 13 * MS, 0.92 * MS]]}) is None
    assert _traced(name, {"modules": [["jit__fused(3)", 0.0, 15 * MS]],
                          "ops": [[ATTN, 1 * MS, 0.5 * MS]]}) is None


class _HbmView:
    """``run.py::View``'s calls that ``conv_moe_fused_hbm_pct`` makes."""

    def __init__(self, modules, busy, hit=(0.0, 0.0), prompts=(300, 700)):
        self.trace = None if modules is None else {"modules": modules}
        self.sizes = {k: SIZES[k] for k in (
            "hidden_size", "num_hidden_layers", "num_dense_layers", "layer_types",
            "num_attention_heads", "num_key_value_heads", "conv_L_cache",
            "intermediate_size", "moe_intermediate_size", "num_experts",
            "vocab_size")}
        self.proc_cfg = dict(prefill_chunk=256, max_new_tokens=512)
        self.peaks = dict(hbm_bytes_per_s=819e9)
        self.run = types.SimpleNamespace(
            pool=types.SimpleNamespace(tokens=np.asarray(prompts, np.int64)))
        self._busy, self._hit = list(busy), hit

    def gauge(self, name):
        assert name == "arkflow_gen_slots_busy"
        return self._busy

    def hist(self, name, **labels):
        assert (name, labels) == (HIT, {"kind": "fused"})
        return self._hit


def test_a_chunk_attends_over_its_prompt_so_far():
    """Chunk i of a prompt of n tokens reads min(i x C, n); a prompt of one
    chunk or less is ONE chunk too (a model with a state pool has no one-shot
    prefill)."""
    tokens = _reader("conv_moe_fused_hbm_pct").chunk_kv_tokens
    assert tokens([300], 256) == pytest.approx((256 + 300) / 2)
    assert tokens([64, 256], 256) == pytest.approx((64 + 256) / 2)
    assert tokens([700, 100], 256) == pytest.approx((256 + 512 + 700 + 100) / 4)
    assert tokens([], 256) == 0.0


def test_fused_hbm_pct_by_hand():
    """128 busy slots (127 lanes decode: the slot whose prompt rides does
    not), the block hitting all 32 experts a layer, 15 ms a fused step: the
    non-expert weights once (0.81 GB), ten routers, 320 experts of 22.0 MB
    (7.05 GB), the lanes' K/V (127 x 756 tokens x 3 layers x 2,048 B) and
    conv rows, the chunk's K/V and conv rows: 8.5 GB in 15 ms is ~69 % of
    819 GB/s. Fewer experts hit, fewer bytes; nothing on the parent (no
    such module, no ``fused`` series), without the gauge or without a trace."""
    from benchmark.lib.costs_conv_gqa_moe import (decode_step_bytes, kv_row_bytes,
                                                  sizes_of, slot_bytes)

    read = _reader("conv_moe_fused_hbm_pct").read
    modules = {"jit__fused(3)": [0.0150, 0.0151, 0.0149], "jit__decode(1)": [0.012]}
    view = _HbmView(modules, [128.0, 128.0], hit=(32.0 * 40, 40.0))
    s = sizes_of(view)
    want = decode_step_bytes(experts_hit=32.0, lanes=127.0, context=127.0 * 756.0, **s)
    want += 3 * kv_row_bytes(kv_heads=8, head_dim=64) * (256 + 300 + 256 + 512 + 700) / 5
    want += 2 * slot_bytes(conv_layers=9, taps=3, hidden=2048)
    got = read(view)
    assert got == pytest.approx(100 * want / 819e9 / 0.0150)
    assert 66 < got < 72 and 8.3e9 < want < 8.7e9
    fewer = read(_HbmView(modules, [128.0], hit=(29.0 * 40, 40.0)))
    assert got - fewer == pytest.approx(100 * 30 * 3 * 2048 * 1792 * 2 / 819e9 / 0.0150)
    parent = {"jit__decode(1)": [0.012], "jit__chunk(2)": [0.0115]}
    assert read(_HbmView(parent, [128.0])) is None
    assert read(_HbmView(modules, [128.0])) is None            # no fused series
    assert read(_HbmView(modules, [], hit=(32.0, 1.0))) is None
    assert read(_HbmView(None, [128.0], hit=(32.0, 1.0))) is None


@pytest.mark.parametrize("name,unit,better,source,layer", [
    ("conv_moe_chunks_fused_pct", "%", "higher", "program_counter", "scheduler, generate"),
    ("conv_moe_fused_step_ms", "ms", "lower", "device_trace", "device step, generate"),
    ("conv_moe_expert_ms_per_fused_step", "ms", "lower", "device_trace", "kernels"),
    ("conv_moe_fused_hbm_pct", "%", "higher", "device_trace", "kernels")])
def test_their_entries(name, unit, better, source, layer):
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better, "source": source,
                     "layer": layer, "moves": "tokens_per_s", "workloads": CELL}
    # appended behind PR 56's entries, in ISSUE 58's order: nothing moved
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index("conv_moe_chunks_fused_pct") == names.index(
        "moe_expert_ms_per_fused_step") + 1
    at = names.index("conv_moe_chunks_fused_pct")
    assert names[at:at + 4] == [
        "conv_moe_chunks_fused_pct", "conv_moe_fused_step_ms",
        "conv_moe_expert_ms_per_fused_step", "conv_moe_fused_hbm_pct"]
    assert layer in {m["layer"] for m in BENCH["per_layer"][:names.index(name)]}
    assert os.path.exists(os.path.join(ROOT, "benchmark/metrics", name + ".py"))
    (tokens,) = [e for e in BENCH["end_to_end"] if e["name"] == "tokens_per_s"]
    assert set(CELL) <= set(tokens["workloads"])
