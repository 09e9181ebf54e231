"""Hand-worked cases for ``benchmark/lib/costs_kda_mla_moe.py`` (the counts
behind the Kimi Linear cell's roofline shares), its readers on a recorded
trace, the cell's entries and its CPU rehearsal. ``python -m pytest
benchmark/tests -q``; outside ``tests/``, so no tier-1 count changes."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import costs_kda_mla_moe as c  # noqa: E402

CELL = "kimilinear_l8.diagnose_backlog"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(ROOT, "benchmark/configs/kimi-linear-48b-a3b-l8-ep8.json")) as f:
    CONFIG = json.load(f)

MIXER = dict(heads=32, head_dim=128)
SIZES = dict(hidden=2304, layers=8, linear_layers=6, dense_layers=1, heads=32,
             nope=128, rope=64, v=128, kv_lora=512, kda_heads=32, kda_dim=128,
             taps=4, dense_width=9216, moe_width=1024, router_outputs=256,
             shared=1, vocab=20_480)
MS = 1e6  # ns


def test_a_slots_state_and_a_token_s_rows():
    # 32 heads x 128 x 128 float32 = 2 MiB a layer
    assert c.state_bytes(**MIXER) == 2 * 2 ** 20 == 32 * 65_536
    # the convs' last 3 inputs over q | k | v = 3 x 4,096 channels, bfloat16
    assert c.conv_channels(**MIXER) == 12_288
    assert c.window_bytes(taps=4, **MIXER) == 3 * 12_288 * 2 == 73_728
    assert c.slot_bytes(linear_layers=6, taps=4, **MIXER) \
        == 6 * (2_097_152 + 73_728) == 13_025_280          # 13.03 MB a slot
    # 129 rows of the pool: 1.68 GB
    assert 129 * 13_025_280 == 1_680_261_120
    # a token over the two latent layers: 2 x (512 + 64) x 2 B at published
    # widths, 2 x (512 + 128) x 2 B as held (zeros behind the key)
    assert c.latent_row_bytes(latent_layers=2, kv_lora=512, rope=64) == 2304
    assert c.latent_row_bytes(latent_layers=2, kv_lora=512, rope=64, held=True) == 2560
    # 137,217 pages of 16 (128 tables of (16,384 + 768) / 16 columns and the
    # scratch page): 5.62 GB; a slot's state = 5,088 tokens of rows
    assert (128 * (16384 + 768) // 16 + 1) * 16 * 2560 == 137_217 * 40_960 == 5_620_408_320
    assert 13_025_280 // 2560 == 5088


def test_mixer_parameters_and_what_the_chip_holds():
    # q | k | v 2304 x 12,288 = 28,311,552; taps 12,288 x 4 = 49,152; the
    # decay's and the gate's pairs 2 x (2304 x 128 + 128 x 4096) = 1,638,400;
    # beta 2304 x 32 = 73,728; o 4096 x 2304 = 9,437,184        = 39,510,016
    assert c.linear_params(hidden=2304, taps=4, **MIXER) == 39_510_016
    # q 2304 x 6144 = 14,155,776; kv_a 2304 x 576 = 1,327,104; kv_b 512 x
    # 8192 = 4,194,304; o 4096 x 2304 = 9,437,184                = 29,114,368
    assert c.attention_params(hidden=2304, heads=32, nope=128, rope=64, v=128,
                              kv_lora=512) == 29_114_368
    # an expert 3 x 2304 x 1024 = 7,077,888; the dense MLP 3 x 2304 x 9216
    assert c.expert_params(hidden=2304, width=1024) == 7_077_888
    # table + head 2 x 47,185,920; 6 KDA 237,060,096; 2 MLA 58,228,736; dense
    # 63,700,992; 7 x 33 experts 1,634,992,128: 2,088,353,792 parameters x 2 B
    # + 7 routers 2304 x 256 x 4 B = 16,515,072                   = 4.19 GB
    assert c.weight_bytes_held(held=32, **SIZES) \
        == 2 * 2_088_353_792 + 16_515_072 == 4_193_222_656


def test_update_bytes_of_128_lanes():
    # 128 lanes x 6 layers x (state read + written 4,194,304 + window 73,728)
    got = c.update_bytes(lanes=128, layers=6, taps=4, **MIXER)
    assert got == 768 * 4_268_032 == 3_277_848_576
    assert got / 819e9 == pytest.approx(4.002e-3, rel=1e-3)


def test_decode_step_bytes_of_128_lanes_at_2200_tokens():
    # head 2304 x 20,480 x 2 B                                =    94,371,840
    # mixers (6 x 39,510,016 + 2 x 29,114,368) x 2 B          =   590,577,664
    # dense MLP 63,700,992 x 2 B                              =   127,401,984
    # routers 7 x 2304 x 256 x 4 B                            =    16,515,072
    # experts (31 hit + 1 shared) x 7 x 14,155,776            = 3,170,893,824
    # latent 2 x (281,600 tokens x 1,152 B + 128 x 32 x 1,088 x 2 B)
    #                                                         =   666,632,192
    # states + windows 128 x 6 x 2 x (2,097,152 + 73,728)     = 3,334,471,680
    got = c.decode_step_bytes(experts_hit=31, lanes=128, context=128 * 2200,
                              **SIZES)
    assert got == (94_371_840 + 590_577_664 + 127_401_984 + 16_515_072
                   + 3_170_893_824 + 666_632_192 + 3_334_471_680) == 8_000_864_256
    # 8.0 GB a step; the state's stream the largest: 41.7 %; 9.77 ms
    assert 3_334_471_680 / got == pytest.approx(0.4168, abs=1e-3)
    assert got / 819e9 == pytest.approx(9.769e-3, rel=1e-3)


def test_chunk_scan_counts_and_the_roof_that_binds():
    shape = dict(tokens=512, layers=6, **MIXER)
    # a token a head, blocks of 64: the count of costs_gdn_gqa_moe at key dim
    # = value dim = 128: 69,632 MACs (the halving levels are the kernel's)
    assert c.chunk_scan_flops(**shape) == 2 * 6 * 512 * 32 * 69_632 \
        == 13_690_208_256
    # a layer: state in and out 4,194,304; a token q, k, v, g and o of 32 x
    # 128 and beta of 32: 20,512 values x 4 B
    assert c.chunk_scan_bytes(**shape) == 6 * (4_194_304 + 512 * 82_048) \
        == 277_217_280
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, binds = c.roofline_seconds(c.chunk_scan_flops(**shape),
                                      c.chunk_scan_bytes(**shape), peaks)
    assert binds == "bytes" and least == pytest.approx(0.3385e-3, rel=1e-3)


def test_sizes_are_read_under_the_source_s_keys():
    assert c.sizes_of(types.SimpleNamespace(sizes=CONFIG)) == SIZES
    assert c.sizes_of(types.SimpleNamespace(sizes={"num_hidden_layers": 4})) is None


# -- the readers on a recorded trace -----------------------------------------------

UPDATE = ("%kda_state_update.3 = (f32[128,32,128]{2,1,0}, f32[774,32,128,128]{3,2,1,0}) "
          "custom-call(s32[128]{0} %r), custom_call_target=\"tpu_custom_call\"")
SCAN = ("%kda_chunk_scan.5 = (f32[1,512,4096]{2,1,0}, f32[774,32,128,128]{3,2,1,0}) "
        "custom-call(s32[1]{0} %r), custom_call_target=\"tpu_custom_call\"")
WALK = ("%mla_paged_attention.2 = bf16[128,1,32,512]{3,2,1,0} custom-call(s32[1]{0} %l), "
        "custom_call_target=\"tpu_custom_call\"")
OTHER = "%fusion.7 = bf16[512,2304]{1,0} fusion(%a), kind=kLoop"


def _reader(name):
    from benchmark.run import load_module

    return load_module("metrics", name).read


def _key(name, **labels):
    return (name, tuple(sorted({"model": "decoder_lm", **labels}.items())))


def _view(ops, modules, *, sizes=None, snaps=({}, {}), busy=(), tokens=(1816,)):
    import numpy as np

    from benchmark.run import View

    view = View.__new__(View)
    view.sizes = dict(CONFIG) if sizes is None else sizes
    view.proc_cfg = dict(CONFIG["engine"]["streams"][0]["pipeline"]["processors"][0])
    view.peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    view._open, view._close = snaps
    view._gauges = {"arkflow_gen_slots_busy": list(busy)}
    view.run = types.SimpleNamespace(pool=types.SimpleNamespace(
        tokens=np.asarray(tokens)))
    view.trace = {"first_device": {"ops": ops, "modules": modules},
                  "modules": {}}
    for name, _, dur in modules:
        view.trace["modules"].setdefault(name.split("(")[0], []).append(dur * 1e-9)
    return view


def _counted(steps=10.0, lanes=128.0, hit=31.0):
    close = {_key("arkflow_gen_ssm_tokens_total", kind="decode"): steps * lanes,
             _key("arkflow_gen_decode_steps_total"): steps,
             _key("arkflow_gen_moe_experts_hit", kind="decode"): (steps * hit, steps)}
    return ({}, close)


def test_the_readers_by_hand():
    """Two decode steps and two chunks. A step's six linear layers: 6 x 1.0 ms
    of the update, 2 x 0.5 ms of the walk; a chunk's: 6 x 1.25 ms of the
    scan; other ops and the other program's kernels do not count."""
    ops, modules = [], []
    t = 0.0
    for prog, length, kernel, each in (("jit__decode(9)", 14 * MS, UPDATE, 1.0 * MS),
                                       ("jit__chunk(7)", 25 * MS, SCAN, 1.25 * MS)) * 2:
        modules.append([prog, t, length])
        at = t
        for _ in range(6):
            ops += [[kernel, at, each], [OTHER, at + each, 0.3 * MS]]
            at += 2 * MS
        if "decode" in prog:
            ops += [[WALK, at, 0.5 * MS], [WALK, at + MS, 0.5 * MS]]
        t += length + MS
    view = _view(ops, modules, snaps=_counted(), busy=[128.0] * 4)
    assert _reader("kda_update_ms_per_step")(view) == pytest.approx(6.0)
    assert _reader("kda_scan_ms_per_chunk")(view) == pytest.approx(7.5)
    # 3,277,848,576 B over 819 GB/s over 6 ms
    assert _reader("kda_update_hbm_pct")(view) == pytest.approx(
        100 * 3_277_848_576 / 819e9 / 6e-3) == pytest.approx(66.7, abs=0.1)
    # the bytes bind: 0.3385 ms over 7.5 ms
    assert _reader("kda_scan_roofline_pct")(view) == pytest.approx(
        100 * 0.3385e-3 / 7.5e-3, rel=1e-3)
    # the accepted reader of the same kernel's time reads this file too
    assert _reader("mla_attn_ms_per_step")(view) == pytest.approx(1.0)
    # 128 lanes at 1,816 + 384 tokens (a prompt and half of the 768 it asks):
    # 666,632,192 B over 1 ms
    assert _reader("kda_mla_attn_hbm_pct")(view) == pytest.approx(
        100 * 666_632_192 / 819e9 / 1e-3)
    # the whole step: 8,000,864,256 B over 14 ms
    assert _reader("kda_moe_decode_hbm_pct")(view) == pytest.approx(
        100 * 8_000_864_256 / 819e9 / 14e-3) == pytest.approx(69.8, abs=0.1)
    # a program with no such kernel, or another source's keys, reads nothing
    none = _view([[OTHER, 0.0, MS]], modules, snaps=_counted(), busy=[128.0])
    other = _view(ops, modules, sizes={"num_hidden_layers": 8}, snaps=_counted(),
                  busy=[128.0])
    for name in ("kda_update_ms_per_step", "kda_update_hbm_pct",
                 "kda_scan_ms_per_chunk", "kda_scan_roofline_pct",
                 "kda_mla_attn_hbm_pct"):
        assert _reader(name)(none) is None, name
    for name in ("kda_update_hbm_pct", "kda_scan_roofline_pct",
                 "kda_mla_attn_hbm_pct", "kda_moe_decode_hbm_pct"):
        assert _reader(name)(other) is None, name
    assert _reader("kda_update_hbm_pct")(_view(ops, modules)) is None  # no counter


def test_entries_and_files():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == BENCH["workloads"][-1] and cell["chips"] == 1
    assert cell["traffic"] == "diagnose_backlog" and len(cell["why"]) <= 200
    assert len(BENCH["workloads"]) == 13
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    conf = BENCH["configs"][-1]
    assert conf["name"] == cell["config"] == CONFIG["name"]
    assert conf["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    names = [m["name"] for m in BENCH["per_layer"]]
    # six of ISSUE 59's nine: BENCHMARK.json may hold 128 per-layer metrics
    # and held 122 (PERF.md section 7)
    new = ["kda_update_ms_per_step", "kda_update_hbm_pct", "kda_scan_ms_per_chunk",
           "kda_scan_roofline_pct", "kda_mla_attn_hbm_pct", "kda_moe_decode_hbm_pct"]
    assert names[-6:] == new and len(names) == 128
    for name in new:
        entry = BENCH["per_layer"][names.index(name)]
        assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s"
        assert entry["layer"] == "kernels"
        assert os.path.exists(os.path.join(ROOT, "benchmark/metrics", name + ".py"))
    assert CELL in next(m for m in BENCH["end_to_end"]
                        if m["name"] == "tokens_per_s")["workloads"]
    for name in ("gen_launch_wake_ms", "bert_step_mxu_pct", "mla_attn_hbm_pct",
                 "moe_decode_hbm_pct", "moe_expert_hbm_pct"):
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL not in entry["workloads"]
    # every published number of the catalog's row is the file's, but the cuts
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["source"] == row["source_url"] == conf["source"]
    traffic = json.load(open(os.path.join(ROOT, "benchmark/traffic/diagnose_backlog.json")))
    assert traffic["lengths"] == {"dist": "lognormal", "median": 1024, "sigma": 1.0,
                                  "min": 128, "max": 16384}
    assert (traffic["batch_rows"], traffic["pool_rows"], traffic["fill_rows"],
            traffic["stratify"], traffic["order"], traffic["settle_s"],
            traffic["trace_seconds"]) == (4, 256, 16, 4, "fixed", 0.5, 4)


#: the listed readers that need no chip: what a CPU rehearsal's traced line
#: has to carry (the others read the profiler's device ops, the chip's memory
#: or the paged kernel's walk, which ``decode_kernel: gather`` does not make)
NO_CHIP = {"slot_occupancy_pct", "compiles_in_window", "gen_prepare_ms",
           "gen_handoff_ms", "gen_apply_ms", "gen_queue_wait_p50_ms",
           "gen_prefill_p50_ms", "gen_token_gap_ms", "gen_uploads_per_step",
           "gen_dispatch_ms", "gen_ready_wait_ms", "gen_fetch_ms",
           "gen_hop_unnamed_ms", "gen_steps_ahead_pct", "setup_init_params_s",
           "setup_place_s", "setup_build_s", "setup_probe_s", "setup_cold_steps_s",
           "setup_compile_s", "setup_compile_cache_hit_pct", "setup_unnamed_s",
           "backend_compiles_in_window", "moe_grouped_chunks_pct",
           "moe_held_assignments_pct", "ssm_masked_pct",
           "gqa_moe_experts_hit_pct"}


def test_the_cell_rehearses_on_the_cpu():
    import subprocess

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    for trace in (0, 1):
        proc = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
             "5900000003", "--seconds", "4", "--trace", str(trace), "--rehearse"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
        assert proc.returncode == 0, proc.stderr[-3000:]
        line = json.loads(proc.stdout.strip().splitlines()[-1])
        assert line["correct"] is True and line["failed"] == 0
        assert line["device"]["platform"] == "cpu"
        listed = {m["name"] for m in BENCH["per_layer" if trace else "end_to_end"]
                  if "workloads" not in m or CELL in m["workloads"]}
        assert set(line["metrics"]) <= listed
        if trace:
            assert NO_CHIP <= set(line["metrics"]) and NO_CHIP <= listed
        else:
            assert set(line["metrics"]) == {"tokens_per_s", "setup_s"}
