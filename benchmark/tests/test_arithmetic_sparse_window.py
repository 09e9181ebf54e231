"""Hand-worked checks of ``lib/costs_sparse_window.py`` at the published
dots3-note-prev sizes, and of the readers that use the harness's gauges."""

from __future__ import annotations

import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import pytest  # noqa: E402

from benchmark.lib import costs_sparse_window as c  # noqa: E402

with open(os.path.join(ROOT, "benchmark/configs/dots3-note-prev-l5-ep8.json")) as f:
    CONFIG = json.load(f)


def test_selected_attention_reads_2048_rows_of_1152_bytes_a_lane_a_layer():
    # one lane, one layer: 2,048 x (512 + 64) x 2 B of rows; the query's 128
    # heads carry (576 in + 512 out) x 2 B each
    rows = 2048 * 1152
    query = 128 * (576 + 512) * 2
    assert c.selected_attention_bytes(heads=128, kv_lora=512, rope=64,
                                      selected=2048, queries=1, layers=1) == rows + query
    assert rows == 2_359_296 and query == 278_528
    # 32 lanes, both indexed layers: 2 x 32 x (2,359,296 + 278,528)
    assert c.selected_attention_bytes(heads=128, kv_lora=512, rope=64,
                                      selected=32 * 2048, queries=32,
                                      layers=2) == 64 * (rows + query)
    # FLOPs: per (head, key) 2 x (576 + 512)
    assert c.selected_attention_flops(heads=128, kv_lora=512, rope=64,
                                      selected=2048, layers=1) == 128 * 2048 * 2176


def test_index_scores_read_256_bytes_a_token_in_context():
    # one lane over a context of 5,000 tokens, one layer: 5,000 x (256 B key
    # + 4 B score out) + the query's 64 x 128 x 2 B + 64 float32 weights
    want = 5000 * 260 + 64 * 128 * 2 + 64 * 4
    assert c.index_scores_bytes(index_heads=64, index_dim=128, context=5000,
                                queries=1, layers=1) == want == 1_316_640
    assert c.index_scores_flops(index_heads=64, index_dim=128, context=5000,
                                layers=2) == 2 * 5000 * 64 * 259


def test_window_attention_reads_513_rows_of_2176_bytes_a_lane_a_layer():
    rows = 513 * 2176
    query = 64 * (1088 + 1024) * 2
    got = c.window_attention_bytes(heads=64, kv_lora=1024, rope=64, window=513,
                                   context=5000, queries=1, layers=1)
    assert got == rows + query and rows == 1_116_288 and query == 270_336
    # a context shorter than the window is read whole
    assert c.window_attention_bytes(heads=64, kv_lora=1024, rope=64, window=513,
                                    context=100, queries=1, layers=3) == 3 * (
        100 * 2176 + query)


def test_layer_counts_read_the_pattern_as_cut():
    assert c.layer_counts(CONFIG) == (2, 3)
    assert len(CONFIG["layer_types"]) == 46  # the published list, whole
    assert c.layer_counts({**CONFIG, "num_hidden_layers": 46}) == (13, 33)


def _view(**gauges):
    view = types.SimpleNamespace(
        sizes=CONFIG, proc_cfg=CONFIG["engine"]["streams"][0]["pipeline"]["processors"][0])
    view.gauge = lambda name: gauges.get(name, [])
    return view


def test_kv_window_live_pct_from_the_two_gauges():
    from benchmark.run import load_module

    read = load_module("metrics", "kv_window_live_pct").read
    pages = 32 * 784                       # slots x ceil(12,544 / 16)
    kept_tokens = 0.4 * pages * 16         # 160,563.2 tokens in kept pages
    # kept: 2 layers x (576 + 128) x 2 B = 2,816 B a token; window: 3 x 1,088
    # x 2 B = 6,528 B; the window pool holds 32 lanes x 34 pages x 16 tokens
    window_live = 32 * 34 * 16 * 6528
    view = _view(arkflow_gen_page_pool_occupancy=[0.4, 0.4],
                 arkflow_gen_kv_live_bytes=[kept_tokens * 2816 + window_live] * 2)
    want = 100.0 * window_live / (kept_tokens * 6528)
    assert read(view) == pytest.approx(want) and 10 < want < 11
    assert read(_view()) is None           # a parent without the gauge


def test_readers_of_the_new_counters_return_none_without_them():
    from benchmark.run import load_module

    view = types.SimpleNamespace(
        sizes=CONFIG, proc_cfg={}, trace=None, peaks={"hbm_bytes_per_s": 819e9},
        counter=lambda name, **kw: 0.0, hist=lambda name, **kw: (0.0, 0.0),
        gauge=lambda name: [])
    for name in ("dsa_selected_pct", "dsa_index_ms_per_step", "dsa_attn_ms_per_step",
                 "dsa_attn_hbm_pct", "dsa_index_hbm_pct", "swa_attn_ms_per_step",
                 "swa_attn_hbm_pct", "moe_held_assignments_pct",
                 "kv_window_live_pct", "dsa_topk_ms_per_step",
                 "kv_window_pages_freed_pct"):
        assert load_module("metrics", name).read(view) is None, name


def test_dsa_topk_ms_reads_the_sorts_of_a_decode_step_by_their_hlo_names():
    """The trace names an op by its whole HLO line (seen on the chip, PR 31):
    two sorts of 2.5 and 1.5 ms inside each of two decode executions count;
    a fusion that consumes a sort's result, and a chunk's sort, do not."""
    from benchmark.run import load_module

    sort = ("%sort.27 = (f32[32,1,12544]{2,1,0}, s32[32,1,12544]{2,1,0}) "
            "sort(f32[32,1,12544]{2,1,0} %fusion.1, s32[32,1,12544]{2,1,0} "
            "%iota.44), dimensions={2}, is_stable=true")
    user = "%fusion.9 = f32[32,1,12544]{2,1,0} fusion(%sort.27), kind=kLoop"
    ms = 1e6                               # the trace's times are in ns
    dev = {"modules": [["jit__decode(1)", 0.0, 20 * ms],
                       ["jit__chunk(2)", 20 * ms, 60 * ms],
                       ["jit__decode(1)", 80 * ms, 20 * ms]],
           "ops": [[sort, 1 * ms, 2.5 * ms], [sort.replace("27", "28"), 5 * ms, 1.5 * ms],
                   [user, 7 * ms, 9 * ms], [sort, 30 * ms, 40 * ms],
                   [sort, 81 * ms, 2.5 * ms], [sort.replace("27", "28"), 85 * ms, 1.5 * ms]]}
    view = types.SimpleNamespace(trace={"first_device": dev})
    read = load_module("metrics", "dsa_topk_ms_per_step").read
    assert read(view) == pytest.approx(4.0)


def test_kv_window_pages_freed_pct_is_freed_tokens_over_tokens_through():
    """A request of 4,896 tokens frees (4,896 - 513) // 16 = 273 of its 306
    window pages on the way; each token is routed 8 times in 4 layers."""
    from benchmark.run import load_module

    counts = {"arkflow_gen_window_pages_freed_total": 273.0,
              "arkflow_gen_moe_assignments_total": 4896 * 8 * 4.0}
    view = types.SimpleNamespace(
        sizes=CONFIG, proc_cfg={"page_size": 16},
        counter=lambda name, **kw: counts[name])
    got = load_module("metrics", "kv_window_pages_freed_pct").read(view)
    assert got == pytest.approx(100.0 * 273 * 16 / 4896) and 89 < got < 90


def test_dsa_decode_step_divides_by_steps_and_indexed_layers():
    counts = {"arkflow_gen_dsa_selected_total": 2 * 10 * 32 * 2048.0,
              "arkflow_gen_dsa_context_total": 2 * 10 * 32 * 5000.0}
    view = types.SimpleNamespace(
        sizes=CONFIG, counter=lambda name, **kw: counts[name],
        hist=lambda name, **kw: (0.0, 10.0), gauge=lambda name: [32.0, 32.0])
    assert c.dsa_decode_step(view) == (32.0, 32 * 2048.0, 32 * 5000.0)


def test_the_configuration_file_holds_the_published_numbers():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "dots3-note-prev")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value and CONFIG[key] != value
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert CONFIG["router_outputs"] == 256 and CONFIG["experts_held"] == [0, 32]
    assert CONFIG["vocab_size"] * 8 == CONFIG["published"]["vocab_size"]
