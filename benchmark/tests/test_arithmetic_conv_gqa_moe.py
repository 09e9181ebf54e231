"""Hand-worked cases for ``benchmark/lib/costs_conv_gqa_moe.py`` (the counts
behind the LFM2-8B-A1B cell's roofline shares), the configuration file's
parameter and cache arithmetic, its readers — the new ones and the readers
the cell shares with ``falconh1_l4`` and ``kexaone_l5`` — on a made-up view,
and the cell rehearsed on the CPU. ``python -m pytest benchmark/tests -q``;
outside ``tests/``, so no tier-1 count changes."""

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

from benchmark.lib import costs_conv_gqa_moe as c  # noqa: E402

CELL = "lfm2_l12.draft_backlog"
with open(os.path.join(ROOT, "benchmark/configs/lfm2-8b-a1b-l12.json")) as f:
    FILE = json.load(f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
SIZES = dict(hidden=2048, layers=12, dense_layers=2, attn_layers=3, heads=32,
             kv_heads=8, head_dim=64, taps=3, dense_width=7168, moe_width=1792,
             experts=32, vocab=65536)


def test_the_file_holds_the_source_and_states_its_cut():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        source = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
    assert FILE["source"] == source["source_url"]
    differ = {k for k, v in source["config"].items() if FILE.get(k, "absent") != v}
    assert differ == set(FILE["reduced"]) == {"num_hidden_layers"}
    assert FILE["published"] == {"num_hidden_layers": 24}
    # the first twelve published layer_types letter for letter: C C A C C C A C C C A C
    kinds = FILE["layer_types"][:FILE["num_hidden_layers"]]
    assert "".join(k[0] for k in kinds).upper() == "CCFCCCFCCCFC"
    assert FILE["first_k_dense_replace"] == FILE["num_dense_layers"] == 2
    assert FILE["head_dim"] == FILE["hidden_size"] // FILE["num_attention_heads"] == 64
    entry = next(e for e in BENCH["configs"] if e["name"] == FILE["name"])
    assert entry["reduced"] == FILE["reduced"] and entry["source"] == FILE["source"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        FILE["name"], "draft_backlog", 1)
    assert CELL in next(m for m in BENCH["end_to_end"]
                        if m["name"] == "tokens_per_s")["workloads"]
    with open(os.path.join(ROOT, "benchmark/traffic/draft_backlog.json")) as f:
        mix = json.load(f)
    assert (mix["batch_rows"], mix["pool_rows"], mix["fill_rows"], mix["stratify"]) == (
        4, 256, 16, 4)
    assert mix["lengths"] == {"dist": "lognormal", "median": 384, "sigma": 0.8,
                              "min": 32, "max": 4096}
    assert sizes_from_the_file() == SIZES


def sizes_from_the_file():
    return c.sizes_of(types.SimpleNamespace(sizes=FILE))


def test_a_token_s_rows_a_slot_s_windows_and_the_pools():
    assert c.kv_row_bytes(kv_heads=8, head_dim=64) == 2048
    assert 3 * 2048 == 6144                                    # a token, three layers
    assert c.slot_bytes(conv_layers=9, taps=3, hidden=2048) == 73728
    p = FILE["engine"]["streams"][0]["pipeline"]["processors"][0]
    assert (p["slots"], p["max_input"] + p["max_new_tokens"], p["page_size"]) == (
        128, 4608, 16)
    pages = 1 + 128 * (4608 // 16)
    assert pages == 36865 and pages * 16 * 6144 == 3_623_976_960   # 3.62 GB of pages
    assert 129 * 73728 == 9_510_912                                 # 9.5 MB of windows
    assert 24 * 2048 == 49152    # 24 GQA layers of the same heads, a token
    w = next(x for x in BENCH["workloads"] if x["name"] == CELL)
    assert FILE["engine"]["streams"][0]["pipeline"]["thread_num"] * 4 == p["slots"]
    assert len(w["why"]) <= 200


def test_parameters_of_the_cut():
    conv = c.conv_params(hidden=2048, taps=3)
    attn = c.attention_params(hidden=2048, heads=32, kv_heads=8, head_dim=64)
    assert conv == 12_582_912 + 4_194_304 + 6144 == 16_783_360
    assert attn == 4_194_304 + 1_048_576 + 1_048_576 + 4_194_304 == 10_485_760
    expert = c.expert_params(hidden=2048, width=1792)
    dense = c.expert_params(hidden=2048, width=7168)
    assert expert == 11_010_048 and dense == 44_040_192
    norms = 2 * 2048 * 12 + 2048 + 3 * 2 * 64
    total = (9 * conv + 3 * attn + 2 * dense + 10 * 32 * expert + 10 * (2048 * 32 + 32)
             + 2 * 65536 * 2048 + norms)
    assert total == 4_062_945_984 and total * 2 == pytest.approx(8.13e9, rel=1e-3)
    # the experts are 87 % of it; one array for table and head would save 268 MB
    assert 10 * 32 * expert / total == pytest.approx(0.867, abs=1e-3)
    assert (total - 65536 * 2048) * 2 == pytest.approx(7.86e9, rel=1e-3)


def test_attention_bytes_of_128_lanes():
    # 128 lanes at a mean context of 900: their K and V on three layers at
    # 2,048 B a token + q in and out back, 32 x 64 x 2 B each a query a layer
    got = c.attention_bytes(kv_heads=8, head_dim=64, heads=32, keys=128 * 900,
                            queries=128, layers=3)
    assert got == 3 * (115_200 * 2048 + 128 * 8192) == 710_934_528
    # one layer at a context of 2,048: 0.54 GB, 0.66 ms at the roof
    one = c.attention_bytes(kv_heads=8, head_dim=64, heads=32, keys=128 * 2049,
                            queries=128, layers=1)
    assert one / 819e9 == pytest.approx(0.657e-3, rel=1e-2)


def test_decode_step_bytes():
    got = c.decode_step_bytes(experts_hit=31.5, lanes=128, context=128 * 900, **SIZES)
    weights = (2048 * 65536 * 2 + (9 * 16_783_360 + 3 * 10_485_760) * 2
               + 2 * 44_040_192 * 2 + 10 * 2048 * 32 * 4
               + 10 * 31.5 * 11_010_048 * 2)
    cache = 3 * 2048 * 115_200 + 2 * 128 * 73728
    assert got == weights + cache == pytest.approx(8.472e9, rel=1e-3)
    # 10.3 ms at 819 GB/s: the floor of a decode step of 128 lanes at 900
    assert got / 819e9 == pytest.approx(10.34e-3, rel=1e-2)
    assert c.expert_product_bytes(hidden=2048, moe_width=1792, experts_hit=31.5,
                                  expert_layers=10) / got == pytest.approx(0.82, abs=0.01)
    # the conv windows, read and written, are 19 MB of it; the K/V 0.71 GB
    assert 2 * 128 * 73728 == 18_874_368


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark/metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _view(sizes, *, trace=True):
    """120 lanes at the mix's mean context, decode steps of 14 ms (the three
    attention calls 1.5 ms together, the experts 9 ms) and chunks of 20 ms
    with 0.9 ms of attention."""
    ops = [("paged_flash_attention.1", 0.5e-3), ("paged_flash_attention.2", 0.5e-3),
           ("paged_flash_attention.3", 0.5e-3), ("moe_expert_swiglu.2", 9e-3),
           ("paged_flash_attention.1", 0.9e-3)]
    dev = {"modules": [("jit__decode", 0, 14e6), ("jit__chunk", 20e6, 20e6)],
           "ops": [(n, 1e6 * i + (20e6 if i == 5 else 0), d * 1e9)
                   for i, (n, d) in enumerate(ops, 1)]}
    pool = types.SimpleNamespace(tokens=np.full(256, 516.0))
    hists = {("arkflow_gen_moe_experts_hit", "decode"): (31.5 * 10, 10),
             ("arkflow_gen_moe_experts_hit", "chunk"): (32.0 * 4, 4),
             ("arkflow_gen_moe_max_load", "decode"): (400.0, 10)}
    counters = {("arkflow_gen_moe_assignments_total", "decode"): 4 * 10 * 1200.0,
                ("arkflow_gen_ssm_tokens_total", "chunk"): 5160.0,
                ("arkflow_gen_ssm_masked_total", "chunk"): 2520.0,
                ("arkflow_gen_attn_tiles_total", "chunk"): 720.0}
    live = 120 * 73728 + 120 * 772 * 6144.0
    gauge = "arkflow_gen_kv_live_bytes"
    snaps = [{(gauge, (("model", "decoder_lm"), ("pool", "kv"))): n * 772 * 6144.0,
              (gauge, (("model", "decoder_lm"), ("pool", "conv"))): n * 73728.0,
              ("arkflow_gen_slots_busy", (("model", "decoder_lm"),)): float(n)}
             for n in (100, 120)]
    return types.SimpleNamespace(
        _open=snaps[0], _close=snaps[1],
        sizes=sizes, peaks={"hbm_bytes_per_s": 819e9},
        proc_cfg={"max_new_tokens": 512, "slots": 128, "page_size": 16,
                  "max_input": 4096},
        trace={"first_device": dev, "modules": {"jit__decode": [14e-3]}} if trace else None,
        run=types.SimpleNamespace(pool=pool),
        hist=lambda name, **lab: hists.get((name, lab.get("kind")), (0.0, 0.0)),
        counter=lambda name, **lab: (
            0.0 if lab.get("product") in ("all_heads", "per_kv_head")
            else counters.get((name, lab.get("kind")), 0.0)),
        gauge=lambda name: {"arkflow_gen_slots_busy": [120.0],
                            "arkflow_gen_kv_live_bytes": [live]}.get(name, []))


NEW = ["narrow_attn_ms_per_step", "narrow_attn_ms_per_chunk", "narrow_attn_hbm_pct",
       "conv_moe_decode_hbm_pct", "conv_state_share_of_cache_pct",
       "conv_moe_expert_hbm_pct"]


def test_new_readers_on_a_made_up_view():
    view = _view(FILE)
    lanes, ctx = 120, 120 * (516 + 256)
    assert _reader("narrow_attn_ms_per_step")(view) == pytest.approx(1.5)
    assert _reader("narrow_attn_ms_per_chunk")(view) == pytest.approx(0.9)
    assert _reader("narrow_attn_hbm_pct")(view) == pytest.approx(
        100 * 3 * (ctx * 2048 + lanes * 8192) / 819e9 / 1.5e-3)
    assert _reader("conv_moe_decode_hbm_pct")(view) == pytest.approx(
        100 * c.decode_step_bytes(experts_hit=31.5, lanes=lanes, context=ctx, **SIZES)
        / 819e9 / 14e-3)
    assert _reader("conv_state_share_of_cache_pct")(view) == pytest.approx(
        100 * 73728 / (73728 + 772 * 6144))
    # the POOL's label is what it reads: a pool twice the size reads twice the share
    key = ("arkflow_gen_kv_live_bytes", (("model", "decoder_lm"), ("pool", "conv")))
    view._open[key] *= 2
    view._close[key] *= 2
    assert _reader("conv_state_share_of_cache_pct")(view) == pytest.approx(
        100 * 2 * 73728 / (2 * 73728 + 772 * 6144))
    # 31.5 experts hit a layer x 3 x 2,048 x 1,792 x 2 B x 10 layers over 9 ms
    assert _reader("conv_moe_expert_hbm_pct")(view) == pytest.approx(
        100 * 31.5 * 22_020_096 * 10 / 819e9 / 9e-3)
    for share in ("narrow_attn_hbm_pct", "conv_moe_decode_hbm_pct",
                  "conv_state_share_of_cache_pct", "conv_moe_expert_hbm_pct"):
        assert 0 < _reader(share)(view) <= 100


def test_shared_readers_are_right_for_this_file_as_it_stands():
    """The readers written for other cells that this one lists, by hand on
    this file's keys: ``num_experts`` 32 counts every expert (all held),
    ``first_k_dense_replace`` 2 is the file's (= ``num_dense_layers``); the
    state-kind counters count for the conv windows under their ``ssm`` names;
    a row-major pool's tiles count under a product of their own
    (``head_run``), so ``attn_per_head_tiles_pct`` finds nothing to read and
    is not listed. The ``moe_*`` readers
    that want ``n_routed_experts`` and the ``*_moe_expert_hbm_pct`` readers
    that want a shared-expert key are NOT listed."""
    view = _view(FILE)
    assert _reader("moe_expert_ms_per_step")(view) == pytest.approx(9.0)
    assert _reader("gqa_moe_experts_hit_pct")(view) == pytest.approx(100 * 31.5 / 32)
    assert _reader("ssm_masked_pct")(view) == pytest.approx(100 * 2520 / 7680)
    assert _reader("attn_per_head_tiles_pct")(view) is None
    for name in ("moe_experts_hit_pct", "moe_chunk_experts_hit_pct",
                 "moe_load_max_over_mean", "moe_expert_hbm_pct"):
        with pytest.raises(KeyError):
            _reader(name)(view)
    assert _reader("gqa_moe_expert_hbm_pct")(view) is None
    listed = {m["name"] for m in BENCH["per_layer"] if CELL in m.get("workloads", [])}
    assert {"moe_expert_ms_per_step", "gqa_moe_experts_hit_pct", "ssm_masked_pct",
            "gen_host_gap_ms", "decode_step_ms",
            "prefill_chunk_ms", "slot_occupancy_pct", "peak_hbm_gb", *NEW} <= listed
    assert not {"attn_per_head_tiles_pct", "moe_experts_hit_pct", "moe_chunk_experts_hit_pct", "moe_expert_hbm_pct",
                "gqa_moe_expert_hbm_pct", "gen_launch_wake_ms", "gen_steps_ahead_pct",
                "bert_step_mxu_pct", "decode_hbm_pct"} & listed


@pytest.mark.parametrize("name", NEW)
def test_readers_find_nothing_on_another_file_or_without_a_trace(name):
    """A configuration of another layout, or a run without a trace or the
    counters (the parent's program on an old cell), leaves the metric out
    and does not raise."""
    other = _view({"hidden_size": 4096, "num_hidden_layers": 6})
    for snap in (other._open, other._close):  # another program: no pool named conv
        for key in [k for k in snap if ("pool", "conv") in k[1]]:
            del snap[key]
    assert _reader(name)(other) is None or name.endswith(("per_step", "per_chunk"))
    blank = _view(FILE, trace=False)
    blank.hist = lambda name, **lab: (0.0, 0.0)
    blank.gauge = lambda name: []
    blank._open = blank._close = {}
    assert _reader(name)(blank) is None


def test_the_cell_rehearsed_on_the_cpu_reports_every_reader_that_needs_no_chip():
    """A ``--rehearse --trace 1`` run of the cell (heads of 16, 8 experts, two
    leading dense conv layers): ``correct``, and on the line every listed
    reader but those that read a kernel's name in a device trace, the chip's
    peaks or its memory, or that count what only ``decode_kernel: paged``
    does (every overlay serves ``gather``): held ``<=``, not ``==``."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "4600000019", "--seconds", "4", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    listed = {m["name"] for m in BENCH["per_layer"]
              if "workloads" not in m or CELL in m["workloads"]}
    needs_chip = {"decode_step_ms", "prefill_chunk_ms", "peak_hbm_gb",
                  "moe_expert_ms_per_step",
                  "gen_host_gap_ms", "narrow_attn_ms_per_step",
                  "narrow_attn_ms_per_chunk", "narrow_attn_hbm_pct",
                  "conv_moe_decode_hbm_pct", "conv_moe_expert_hbm_pct"}
    assert listed - set(line["metrics"]) <= needs_chip
    assert {"conv_state_share_of_cache_pct", "ssm_masked_pct",
            "gqa_moe_experts_hit_pct", "slot_occupancy_pct"} <= set(line["metrics"])
