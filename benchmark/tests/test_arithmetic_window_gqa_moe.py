"""Hand-worked cases for ``benchmark/lib/costs_window_gqa_moe.py`` (the
counts behind the K-EXAONE cell's roofline shares) and its readers on a
made-up view. ``python -m pytest benchmark/tests -q``; outside ``tests/``, so
no tier-1 count changes."""

import importlib.util
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import costs_window_gqa_moe as c  # noqa: E402

HEADS = dict(kv_heads=8, head_dim=128)
with open(os.path.join(ROOT, "benchmark/configs/k-exaone-236b-a23b-l5-ep8.json")) as f:
    FILE = json.load(f)


def test_a_token_s_row_and_the_pools():
    assert c.kv_row_bytes(**HEADS) == 4096
    # 48 slots x 8,448 tokens kept on one layer; 48 rings x 41 pages x 16 on four
    assert 48 * 8448 * 4096 == 1_660_944_384
    assert 4 * 4096 * 48 * 41 * 16 == 515_899_392
    assert 5 * 48 * 8448 * 4096 == 8_304_721_920  # all five layers kept


def test_parameters_of_the_cut():
    attn = c.attention_params(hidden=6144, heads=64, **HEADS)
    assert attn == 113_246_208
    expert = c.expert_params(hidden=6144, width=2048)
    assert expert == 37_748_736 and c.expert_params(hidden=6144, width=18432) == 339_738_624
    layer = attn + 17 * expert + 6144 * 128
    assert layer == 755_761_152
    total = (attn + 339_738_624) + 4 * layer + 2 * 6144 * 19200
    assert total == 3_711_959_040 and total * 2 == pytest.approx(7.42e9, rel=1e-3)


def test_attention_bytes_of_48_lanes():
    # sliding: 48 lanes x 128 keys x 4,096 B x 4 layers + q in / out 48 x 2 x 64 x 128 x 2 B x 4
    keys = c.window_keys(context=48 * 1557.0, queries=48, window=128)
    assert keys == 48 * 128
    got = c.attention_bytes(heads=64, keys=keys, queries=48, layers=4, **HEADS)
    assert got == 4 * (6144 * 4096 + 48 * 32768) == 106_954_752
    # full: the whole context of every lane on one layer
    got = c.attention_bytes(heads=64, keys=48 * 1557, queries=48, layers=1, **HEADS)
    assert got == 74_736 * 4096 + 48 * 32768 == 307_691_520
    # a context shorter than the window counts itself
    assert c.window_keys(context=100.0, queries=2, window=128) == 100.0


def test_decode_step_bytes():
    sizes = dict(hidden=6144, layers=5, dense_layers=1, heads=64, dense_width=18432,
                 moe_width=2048, router_outputs=128, shared=1, vocab=19200,
                 full_layers=1, sliding_layers=4, window=128, **HEADS)
    got = c.decode_step_bytes(experts_hit=15.0, lanes=48, context=48 * 1557, **sizes)
    weights = (6144 * 19200 * 2 + (113_246_208 + 339_738_624) * 2
               + 4 * (113_246_208 * 2 + 6144 * 128 * 4) + 4 * 16 * 37_748_736 * 2)
    cache = 4096 * (74_736 + 4 * 6144)
    assert got == weights + cache == 7_299_072_000
    # 8.9 ms at 819 GB/s: the floor of a decode step of 48 lanes
    assert got / 819e9 == pytest.approx(8.91e-3, rel=1e-2)
    # the experts hit are 66 % of it
    assert c.expert_product_bytes(hidden=6144, moe_width=2048, shared=1,
                                  experts_hit=15.0, expert_layers=4) / got == \
        pytest.approx(0.66, abs=0.01)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark/metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _view(sizes, *, trace=True):
    """48 lanes at the mix's mean context, decode steps of 12 ms: the window
    kernel 0.4 ms, the full kernel 3 ms, the experts 6 ms."""
    ops = [("paged_window_attention.1", 0.4e-3), ("paged_flash_attention", 3e-3),
           ("moe_expert_swiglu.2", 6e-3)]
    dev = {"modules": [("jit__decode", 0, 12e6)],  # (name, start ns, ns)
           "ops": [(n, 1e6 * i, d * 1e9) for i, (n, d) in enumerate(ops, 1)]}
    pool = types.SimpleNamespace(tokens=__import__("numpy").full(128, 1429.0))
    hists = {("arkflow_gen_moe_experts_hit", "decode"): (15.0 * 10, 10),
             ("arkflow_gen_moe_max_load", "decode"): (60.0, 10)}
    return types.SimpleNamespace(
        sizes=sizes, peaks={"hbm_bytes_per_s": 819e9},
        proc_cfg={"max_new_tokens": 256, "slots": 48, "page_size": 16,
                  "max_input": 8192},
        trace={"first_device": dev, "modules": {"jit__decode": [12e-3]}} if trace else None,
        run=types.SimpleNamespace(pool=pool),
        hist=lambda name, **lab: hists.get((name, lab.get("kind")), (0.0, 0.0)),
        counter=lambda name, **lab: 48 * 8 * 4 * 10.0,
        gauge=lambda name: {"arkflow_gen_slots_busy": [48.0],
                            "arkflow_gen_page_pool_occupancy": [0.2],
                            "arkflow_gen_kv_live_bytes": [
                                0.2 * 25344 * 16 * 4096 + 48 * 9 * 16 * 16384.0]}.get(name, []))


def test_readers_on_a_made_up_view():
    view = _view(FILE)
    lanes, ctx = 48, 48 * (1429 + 128)
    assert _reader("gqa_window_attn_ms_per_step")(view) == pytest.approx(0.4)
    assert _reader("gqa_full_attn_ms_per_step")(view) == pytest.approx(3.0)
    assert _reader("gqa_moe_expert_ms_per_step")(view) == pytest.approx(6.0)
    assert _reader("gqa_window_attn_hbm_pct")(view) == pytest.approx(
        100 * 106_954_752 / 819e9 / 0.4e-3)
    assert _reader("gqa_full_attn_hbm_pct")(view) == pytest.approx(
        100 * (ctx * 4096 + lanes * 32768) / 819e9 / 3e-3)
    assert _reader("gqa_moe_expert_hbm_pct")(view) == pytest.approx(
        100 * 4 * 16 * 37_748_736 * 2 / 819e9 / 6e-3)
    assert _reader("gqa_moe_experts_hit_pct")(view) == pytest.approx(100 * 15 / 16)
    assert _reader("gqa_moe_decode_hbm_pct")(view) == pytest.approx(
        100 * 7_299_072_000 / 819e9 / 12e-3)
    # 48 lanes hold 9 window pages each where 20 % of 405,504 kept tokens live
    assert _reader("kv_window_pool_live_pct")(view) == pytest.approx(
        100 * 48 * 9 * 16 / (0.2 * 25344 * 16))
    for share in ("gqa_window_attn_hbm_pct", "gqa_full_attn_hbm_pct",
                  "gqa_moe_expert_hbm_pct", "gqa_moe_decode_hbm_pct"):
        assert 0 < _reader(share)(view) <= 100


@pytest.mark.parametrize("name", [
    "gqa_window_attn_ms_per_step", "gqa_full_attn_ms_per_step",
    "gqa_window_attn_hbm_pct", "gqa_full_attn_hbm_pct", "gqa_moe_decode_hbm_pct",
    "kv_window_pool_live_pct", "gqa_moe_expert_ms_per_step",
    "gqa_moe_expert_hbm_pct", "gqa_moe_experts_hit_pct"])
def test_readers_find_nothing_on_another_file_or_without_a_trace(name):
    """A configuration of another layout, or a run without a trace or the
    counters, leaves the metric out and does not raise."""
    other = {"hidden_size": 4096, "num_hidden_layers": 6}
    assert _reader(name)(_view(other)) is None or name.endswith("ms_per_step")
    blank = _view(FILE, trace=False)
    blank.hist = lambda name, **lab: (0.0, 0.0)
    blank.gauge = lambda name: []
    assert _reader(name)(blank) is None
