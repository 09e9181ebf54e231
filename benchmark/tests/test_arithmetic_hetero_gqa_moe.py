"""Hand-worked cases for ``benchmark/lib/costs_hetero_gqa_moe.py`` (the
counts behind the MiMo-V2.5 cell's roofline shares), the configuration
file's parameter and cache arithmetic, its readers — the new ones and the
``moe_*`` / ``kv_window_pages_freed_pct`` readers the cell shares with
``dots3_l5`` — on a made-up view, and the cell rehearsed on the CPU.
``python -m pytest benchmark/tests -q``; outside ``tests/``, so no tier-1
count changes."""

import importlib.util
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import costs_hetero_gqa_moe as c  # noqa: E402

CELL = "mimo_l7.long_reason_backlog"
WIDTHS = dict(key_dim=192, value_dim=128)
with open(os.path.join(ROOT, "benchmark/configs/mimo-v2.5-l7-ep16.json")) as f:
    FILE = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def test_the_file_holds_the_source_and_states_its_cut():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        source = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
    assert FILE["source"] == source["source_url"]
    differ = {k for k, v in source["config"].items() if FILE.get(k, "absent") != v}
    assert differ == set(FILE["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert FILE["published"] == {k: source["config"][k] for k in FILE["reduced"]}
    assert FILE["layer_types"][:7] == [
        {0: "full_attention", 1: "sliding_attention"}[k]
        for k in FILE["hybrid_layer_pattern"][:7]]
    assert FILE["first_k_dense_replace"] == FILE["moe_layer_freq"].index(1) == 1
    assert int(FILE["head_dim"] * FILE["partial_rotary_factor"]) == 64
    entry = next(e for e in BENCH["configs"] if e["name"] == FILE["name"])
    assert entry["reduced"] == FILE["reduced"] and entry["source"] == FILE["source"]


def test_a_token_s_rows_and_the_pools():
    full = dict(kv_heads=4, **WIDTHS)
    swa = dict(kv_heads=8, **WIDTHS)
    assert c.kv_row_bytes(**full) == 2560 and c.kv_row_bytes(**swa) == 5120
    # as held: a 192-wide key in two parts of 128 lanes
    assert c.held_row_bytes(**full) == 3072 and c.held_row_bytes(**swa) == 6144
    assert c.held_row_bytes(kv_heads=8, key_dim=128, value_dim=128) == 4096
    # 64 slots x 13,312 tokens kept on two layers; 64 rings x 41 pages x 16 on five
    assert 2 * 3072 * 64 * 13312 == 5_234_491_392      # 5.23 GB held
    assert 2 * 2560 * 64 * 13312 == 4_362_076_160      # 4.36 GB at the published widths
    assert 5 * 6144 * 64 * 41 * 16 == 1_289_748_480    # 1.29 GB held
    assert 5 * 5120 * 64 * 41 * 16 == 1_074_790_400
    assert (128 + 512 - 2) // 16 + 2 == 41 and 13312 // 16 == 832
    p = FILE["engine"]["streams"][0]["pipeline"]["processors"][0]
    assert (p["slots"], p["max_input"] + p["max_new_tokens"]) == (64, 13312)


def test_parameters_of_the_cut():
    full = c.attention_params(hidden=4096, heads=64, kv_heads=4, **WIDTHS)
    swa = c.attention_params(hidden=4096, heads=64, kv_heads=8, **WIDTHS)
    assert full == 50_331_648 + 3_145_728 + 2_097_152 + 33_554_432 == 89_128_960
    assert swa == 94_371_840
    expert = c.expert_params(hidden=4096, width=2048)
    assert expert == 25_165_824 and c.expert_params(hidden=4096, width=16384) == 201_326_592
    total = (2 * full + 5 * swa + 201_326_592 + 6 * 4096 * 256
             + 6 * 16 * expert + 2 * 4096 * 19072)
    assert total == 3_429_892_096 and total * 2 == pytest.approx(6.86e9, rel=1e-3)
    # at ep 8 (32 experts held) the same cut: 11.7 GB
    assert (total + 6 * 16 * expert) * 2 == pytest.approx(11.7e9, rel=5e-3)


def test_attention_bytes_of_32_lanes():
    # sliding: 32 lanes x 128 keys x 5,120 B x 5 layers + q in 64 x 192 x 2 B
    # and out 64 x 128 x 2 B a query a layer
    keys = c.window_keys(context=32 * 5500.0, queries=32, window=128)
    assert keys == 32 * 128
    got = c.attention_bytes(heads=64, kv_heads=8, keys=keys, queries=32, layers=5, **WIDTHS)
    assert got == 5 * (4096 * 5120 + 32 * 40960) == 111_411_200
    # full: the whole context of every lane on two layers at 2,560 B
    got = c.attention_bytes(heads=64, kv_heads=4, keys=32 * 5500, queries=32, layers=2, **WIDTHS)
    assert got == 2 * (176_000 * 2560 + 32 * 40960) == 903_741_440


def test_decode_step_bytes():
    sizes = dict(hidden=4096, layers=7, dense_layers=1, heads=64, kv_heads=4,
                 swa_kv_heads=8, dense_width=16384, moe_width=2048,
                 router_outputs=256, vocab=19072, full_layers=2, sliding_layers=5,
                 window=128, **WIDTHS)
    got = c.decode_step_bytes(experts_hit=10.0, lanes=32, context=32 * 5500, **sizes)
    weights = (4096 * 19072 * 2 + (2 * 89_128_960 + 5 * 94_371_840) * 2
               + 201_326_592 * 2 + 6 * 4096 * 256 * 4 + 6 * 10 * 25_165_824 * 2)
    cache = 2 * 2560 * 176_000 + 5 * 5120 * 4096
    assert got == weights + cache == 5_910_167_552
    # 7.2 ms at 819 GB/s: the floor of a decode step of 32 lanes at 5.5k
    assert got / 819e9 == pytest.approx(7.22e-3, rel=1e-2)
    assert c.expert_product_bytes(hidden=4096, moe_width=2048, experts_hit=10.0,
                                  expert_layers=6) / got == pytest.approx(0.51, abs=0.01)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark/metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _view(sizes, *, trace=True):
    """32 lanes at the mix's mean context, decode steps of 12 ms (the window
    kernel 0.6 ms, the full kernel 2 ms, the experts 5 ms) and chunks of 40
    ms with 9 ms of the full layers' kernel."""
    ops = [("paged_window_attention.1", 0.6e-3), ("paged_flash_attention", 2e-3),
           ("moe_expert_swiglu.2", 5e-3), ("paged_flash_attention.3", 9e-3)]
    dev = {"modules": [("jit__decode", 0, 12e6), ("jit__chunk", 20e6, 40e6)],
           "ops": [(n, 1e6 * i + (20e6 if i == 4 else 0), d * 1e9)
                   for i, (n, d) in enumerate(ops, 1)]}
    pool = types.SimpleNamespace(tokens=np.full(128, 4988.0))
    hists = {("arkflow_gen_moe_experts_hit", "decode"): (10.0 * 10, 10),
             ("arkflow_gen_moe_experts_hit", "chunk"): (16.0 * 4, 4),
             ("arkflow_gen_moe_max_load", "decode"): (40.0, 10)}
    counters = {"arkflow_gen_moe_assignments_total": 8 * 6 * 10_000.0,
                "arkflow_gen_moe_held_assignments_total": 8 * 6 * 625.0,
                "arkflow_gen_window_pages_freed_total": 560.0}
    return types.SimpleNamespace(
        sizes=sizes, peaks={"hbm_bytes_per_s": 819e9},
        proc_cfg={"max_new_tokens": 1024, "slots": 64, "page_size": 16,
                  "max_input": 12288},
        trace={"first_device": dev, "modules": {"jit__decode": [12e-3]}} if trace else None,
        run=types.SimpleNamespace(pool=pool),
        hist=lambda name, **lab: hists.get((name, lab.get("kind")), (0.0, 0.0)),
        counter=lambda name, **lab: counters.get(name, 0.0),
        gauge=lambda name: {"arkflow_gen_slots_busy": [32.0],
                            "arkflow_gen_page_pool_occupancy": [0.2],
                            "arkflow_gen_kv_live_bytes": [
                                0.2 * 53248 * 16 * 2 * 3072 + 32 * 9 * 16 * 5 * 6144.0]}.get(name, []))


def test_new_readers_on_a_made_up_view():
    view = _view(FILE)
    lanes, ctx = 32, 32 * (4988 + 512)
    assert _reader("hetero_window_attn_ms_per_step")(view) == pytest.approx(0.6)
    assert _reader("hetero_full_attn_ms_per_step")(view) == pytest.approx(2.0)
    assert _reader("hetero_full_attn_ms_per_chunk")(view) == pytest.approx(9.0)
    assert _reader("hetero_window_attn_hbm_pct")(view) == pytest.approx(
        100 * 111_411_200 / 819e9 / 0.6e-3)
    assert _reader("hetero_full_attn_hbm_pct")(view) == pytest.approx(
        100 * 2 * (ctx * 2560 + lanes * 40960) / 819e9 / 2e-3)
    assert _reader("hetero_moe_expert_hbm_pct")(view) == pytest.approx(
        100 * 6 * 10 * 25_165_824 * 2 / 819e9 / 5e-3)
    assert _reader("hetero_moe_decode_hbm_pct")(view) == pytest.approx(
        100 * 5_910_167_552 / 819e9 / 12e-3)
    # 32 lanes hold 9 window pages each beside a fifth of 851,968 kept tokens
    window, kept = 32 * 9 * 16 * 5 * 6144, 0.2 * 53248 * 16 * 2 * 3072
    assert _reader("kv_window_share_of_cache_pct")(view) == pytest.approx(
        100 * window / (window + kept))
    for share in ("hetero_window_attn_hbm_pct", "hetero_full_attn_hbm_pct",
                  "hetero_moe_expert_hbm_pct", "hetero_moe_decode_hbm_pct",
                  "kv_window_share_of_cache_pct"):
        assert 0 < _reader(share)(view) <= 100


def test_shared_readers_are_right_for_this_file_as_it_stands():
    """The readers written for ``dots3_l5`` that the cell lists, by hand on
    this file's keys: ``n_routed_experts`` 16 counts the experts HELD (what
    the histograms count since a share is held), ``first_k_dense_replace`` 1
    and ``sliding_window_size`` 128 are the file's. ``moe_expert_hbm_pct`` is
    NOT listed: it adds ``n_shared_experts``, null in this source (the cell
    has ``hetero_moe_expert_hbm_pct``)."""
    view = _view(FILE)
    assert _reader("moe_expert_ms_per_step")(view) == pytest.approx(5.0)
    assert _reader("moe_experts_hit_pct")(view) == pytest.approx(100 * 10 / 16)
    assert _reader("moe_chunk_experts_hit_pct")(view) == pytest.approx(100.0)
    # 625 of 10,000 tokens' pairs fall on the 16 of 256 held: a sixteenth
    assert _reader("moe_held_assignments_pct")(view) == pytest.approx(6.25)
    # 10,000 tokens went through the cache; 560 pages of 16 were freed
    assert _reader("kv_window_pages_freed_pct")(view) == pytest.approx(89.6)
    with pytest.raises(TypeError):
        _reader("moe_expert_hbm_pct")(view)
    listed = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])}
    assert {"moe_expert_ms_per_step", "moe_experts_hit_pct", "moe_chunk_experts_hit_pct",
            "moe_held_assignments_pct", "kv_window_pages_freed_pct",
            "hetero_moe_expert_hbm_pct"} <= listed
    assert not {"moe_expert_hbm_pct", "gen_host_gap_ms", "gen_launch_wake_ms",
                "decode_hbm_pct"} & listed


NEW = ["hetero_window_attn_ms_per_step", "hetero_window_attn_hbm_pct",
       "hetero_full_attn_ms_per_step", "hetero_full_attn_hbm_pct",
       "hetero_full_attn_ms_per_chunk", "hetero_moe_expert_hbm_pct",
       "hetero_moe_decode_hbm_pct", "kv_window_share_of_cache_pct"]


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_on_another_file_or_without_a_trace(name):
    """A configuration of another layout, or a run without a trace or the
    counters (the parent's program on an old cell), leaves the metric out
    and does not raise."""
    other = {"hidden_size": 4096, "num_hidden_layers": 6}
    assert _reader(name)(_view(other)) is None or name.endswith(("per_step", "per_chunk"))
    blank = _view(FILE, trace=False)
    blank.hist = lambda name, **lab: (0.0, 0.0)
    blank.gauge = lambda name: []
    assert _reader(name)(blank) is None


def test_the_cell_rehearsed_on_the_cpu_reports_every_reader_that_needs_no_chip():
    """A ``--rehearse --trace 1`` run of the cell (K of 192 in two parts, V of
    128, 2 / 4 K/V heads, the sink, a window of 16): ``correct``, and on the
    line every listed reader but those that read a kernel's name in a device
    trace, the chip's peaks or its memory."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "4", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    listed = {m["name"] for m in BENCH["per_layer"]
              if "workloads" not in m or CELL in m["workloads"]}
    needs_chip = {"decode_step_ms", "prefill_chunk_ms", "peak_hbm_gb",
                  "moe_expert_ms_per_step", *(n for n in NEW if n.startswith("hetero_"))}
    assert listed - set(line["metrics"]) == needs_chip
