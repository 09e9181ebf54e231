"""Hand-worked cases for ``benchmark/lib/costs_mhc_mla_moe.py`` and the
readers the ``xing4_l10`` cell lists (PR 53): this PR's four on a made-up
trace, and each OLDER reader the cell is listed under on this file's
spellings (``n_routed_experts`` counts the experts HELD, ``router_outputs``
the router's). ``python -m pytest benchmark/tests -q``; outside ``tests/``."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import costs_mhc_mla_moe as c  # noqa: E402

CELL = "xing4_l10.longdoc_backlog"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
with open(os.path.join(ROOT, "benchmark/configs/xing4.0-29b-a4b-l10-ep4.json")) as f:
    CONFIG = json.load(f)
XING = dict(hidden=3584, heads=32, nope=128, rope=64, v=128, kv_lora=512,
            q_lora=768)
MS = 1e6


def test_the_published_count_is_29_5_b_and_the_cut_2_2_b():
    # q_a 3584 x 768 = 2,752,512; q_b 768 x 6144 = 4,718,592; kv_a 3584 x 576
    # = 2,064,384; kv_b 512 x 8192 = 4,194,304; o 4096 x 3584 = 14,680,064
    attn = c.attention_params(**XING)
    assert attn == 28_409_856
    mixing = 2 * c.mix_leaves_bytes(hidden=3584, n=4) // 4
    assert mixing == 2 * (14336 * 24 + 24 + 3) == 688_182       # 0.69 M a layer
    dense = 3 * 3584 * 9216
    expert = 3 * 3584 * 1024
    assert (dense, expert) == (99_090_432, 11_010_048)
    router = 3584 * 64
    table_head = 2 * 3584 * 131072
    layer_dense = attn + mixing + dense
    layer_expert = attn + mixing + router + 65 * expert
    whole = table_head + 2 * layer_dense + 38 * layer_expert
    assert whole == pytest.approx(29.5e9, rel=0.01)
    cut = 2 * 3584 * 32768 + 2 * layer_dense + 8 * (attn + mixing + router + 17 * expert)
    assert cut == pytest.approx(2.223e9, rel=0.005)             # 4.45 GB bf16


def test_mixing_bytes_a_token_a_sub_layer():
    # before: 4 streams in 4 x 7,168 B, the input out 7,168 B, 24 floats out;
    # after: 4 + 1 rows in, 4 rows out, 24 floats in: (3 x 4 + 2) x 7,168 + 192
    per_token = c.mix_bytes(tokens=1, hidden=3584, n=4, sub_layers=1) \
        - c.mix_leaves_bytes(hidden=3584, n=4)
    assert per_token == 14 * 7168 + 2 * 96 == 100_544           # "~100 KB"
    chunk = c.mix_bytes(tokens=512, hidden=3584, n=4, sub_layers=20)
    assert chunk == 20 * (512 * 100_544 + 4 * (14336 * 24 + 27))
    assert chunk / 819e9 == pytest.approx(1.29e-3, rel=0.01)    # 1.3 ms a chunk


def test_decode_step_bytes_by_hand():
    # head 3584 x 32,768 x 2                                  =   234,881,024
    # attention 10 x 28,409,856 x 2                           =   568,197,120
    # dense SwiGLU 2 x 99,090,432 x 2                         =   396,361,728
    # router 8 x 3584 x 64 x 4                                =     7,340,032
    # experts 8 x (15 + 1) x 11,010,048 x 2                   = 2,818,572,288
    # mixing 20 x (32 x 100,544 + 1,376,364)                  =    91,875,440
    got = c.decode_step_bytes(
        layers=10, dense_layers=2, dense_width=9216, moe_width=1024,
        router_outputs=64, shared=1, vocab=32768, n=4, experts_hit=15,
        kv_tokens=0, lanes=32, **XING)
    assert got == 234_881_024 + 568_197_120 + 396_361_728 + 7_340_032 \
        + 2_818_572_288 + 91_875_440
    # 32 lanes at 7,098 tokens: rows x 10 layers x 576 x 2 B = 2.6 GB
    with_cache = c.decode_step_bytes(
        layers=10, dense_layers=2, dense_width=9216, moe_width=1024,
        router_outputs=64, shared=1, vocab=32768, n=4, experts_hit=15,
        kv_tokens=32 * 7098, lanes=32, **XING)
    assert with_cache - got == 32 * 7098 * 10 * 1152


# -- the readers ------------------------------------------------------------------

PRE = ("%mhc_pre.3 = (bf16[512,3584]{1,0}, f32[512,128]{1,0}) custom-call(f32[2,24]{1,0} "
       "%p, bf16[512,14336]{1,0} %x), custom_call_target=\"tpu_custom_call\"")
POST = ("%mhc_post.4 = bf16[512,14336]{1,0} custom-call(bf16[512,14336]{1,0} %x), "
        "custom_call_target=\"tpu_custom_call\"")
WALK = ("%mla_paged_attention.2 = bf16[32,1,32,512]{3,2,1,0} custom-call(s32[1]{0} %l), "
        "custom_call_target=\"tpu_custom_call\"")
EXPERT = ("%moe_expert_swiglu.9 = bf16[32,3584]{1,0} custom-call(s32[1]{0} %l), "
          "custom_call_target=\"tpu_custom_call\"")
OTHER = "%fusion.7 = bf16[512,3584]{1,0} fusion(%a), kind=kLoop"
#: an op that READS a kernel's result carries its name too, and is not it
READS = "%reshape.861 = bf16[1,512,4,3584]{3,2,1,0} reshape(bf16[512,14336]{1,0} %mhc_post.35)"


def _reader(name):
    from benchmark.run import load_module

    return load_module("metrics", name).read


def _view(ops, modules, *, sizes=None, snaps=({}, {}), busy=(), tokens=(7000,)):
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


def test_the_mixing_readers_by_hand():
    """Two chunks and two decode steps. A chunk's twenty sub-layers: 20 x
    (0.04 + 0.05) ms of the two kernels = 1.8 ms; a step's 20 x (0.011 +
    0.008) = 0.38 ms; other ops and the other program's kernels do not count.
    (No share of the HBM roof beside them: a chunk's streams, 14.7 MB, stay in
    fast memory between the two kernels of a sub-layer, so their 1.057 GB of
    "needed" bytes a chunk are not HBM's to move and a share read 138 %.)"""
    ops, modules = [], []
    t = 0.0
    for prog, length, pre, post in (("jit__chunk(7)", 60 * MS, 0.04 * MS, 0.05 * MS),
                                    ("jit__decode(9)", 20 * MS, 0.011 * MS, 0.008 * MS)) * 2:
        modules.append([prog, t, length])
        at = t
        for _ in range(20):
            ops += [[PRE, at, pre], [OTHER, at + pre, 0.4 * MS],
                    [READS, at + pre + 0.4 * MS, 0.05 * MS],
                    [POST, at + pre + 0.5 * MS, post]]
            at += 0.9 * MS
        t += length + 1 * MS
    view = _view(ops, modules)
    assert _reader("mhc_mix_ms_per_chunk")(view) == pytest.approx(1.8)
    assert _reader("mhc_mix_ms_per_step")(view) == pytest.approx(0.38)
    # a program with no such kernel, or one residual stream, reads nothing
    none = _view([[OTHER, 0.0, MS]], modules)
    for name in ("mhc_mix_ms_per_chunk", "mhc_mix_ms_per_step"):
        assert _reader(name)(none) is None
    one = _view(ops, modules, sizes={k: v for k, v in CONFIG.items() if k != "hc_mult"})
    assert _reader("mhc_moe_decode_hbm_pct")(one) is None
    empty = _view([], [])
    empty.trace = None
    for name in ("mhc_mix_ms_per_chunk", "mhc_mix_ms_per_step",
                 "mhc_moe_decode_hbm_pct"):
        assert _reader(name)(empty) is None


def _key(name, kind):
    return (name, (("kind", kind), ("model", "decoder_lm")))


def _routing(steps=100, hit=15.0, pairs_a_layer=128.0):
    """Snapshots in which ``steps`` decode steps hit ``hit`` held experts a
    layer (the histogram sums the layers' MEAN) and routed 32 lanes x 4."""
    close = {_key("arkflow_gen_moe_experts_hit", "decode"): (hit * steps, steps),
             _key("arkflow_gen_moe_max_load", "decode"): (9.0 * steps, steps),
             _key("arkflow_gen_moe_assignments_total", "decode"): pairs_a_layer * 8 * steps,
             _key("arkflow_gen_moe_held_assignments_total", "decode"): 0.25 * pairs_a_layer * 8 * steps}
    return ({}, close)


def test_the_whole_step_s_share_by_hand():
    """A 30 ms decode step, 32 busy lanes, prompts of 7,000 (+ 256 of the
    512 new): needed bytes as ``test_decode_step_bytes_by_hand`` counts them
    with 232,192 attended rows, over 819 GB/s and 30 ms."""
    modules = [["jit__decode(9)", 0.0, 30 * MS], ["jit__decode(9)", 31 * MS, 30 * MS]]
    view = _view([[WALK, 1.0, 12 * MS]], modules, snaps=_routing(), busy=[32.0] * 5)
    need = c.decode_step_bytes(
        layers=10, dense_layers=2, dense_width=9216, moe_width=1024,
        router_outputs=64, shared=1, vocab=32768, n=4, experts_hit=15.0,
        kv_tokens=32 * 7256.0, lanes=32.0, **XING)
    assert need == pytest.approx(6.79e9, rel=0.01)
    assert _reader("mhc_moe_decode_hbm_pct")(view) == pytest.approx(
        100 * need / 819e9 / 30e-3) == pytest.approx(27.6, abs=0.1)


def test_the_older_readers_read_this_file_s_spellings_right():
    """The cell is listed under these because each, by hand, reads what it
    says on this configuration's keys: the walk's bytes at the PUBLISHED
    rope width (64, not the 128 lanes held), the experts hit over the 16
    held, the eight expert layers, the pairs that land on the held share."""
    modules = [["jit__decode(9)", 0.0, 30 * MS], ["jit__chunk(7)", 31 * MS, 60 * MS]]
    ops = [[WALK, 1.0, 12 * MS], [EXPERT, 14 * MS, 4 * MS],
           [EXPERT.replace("swiglu", "grouped"), 40 * MS, 9 * MS]]
    view = _view(ops, modules, snaps=_routing(), busy=[32.0] * 5)
    assert _reader("mla_attn_ms_per_step")(view) == pytest.approx(12.0)
    # rows 232,192 x 1,152 B + 32 queries x 32 heads x (576 + 512) x 2 B, x 10
    walk = 10 * (232_192 * 1152 + 32 * 32 * 1088 * 2)
    assert _reader("mla_attn_hbm_pct")(view) == pytest.approx(
        100 * walk / 819e9 / 12e-3) == pytest.approx(27.44, abs=0.01)
    assert _reader("moe_expert_ms_per_step")(view) == pytest.approx(4.0)
    assert _reader("moe_expert_ms_per_chunk")(view) == pytest.approx(9.0)
    # 8 layers x (15 + 1) experts x 22,020,096 B over 819 GB/s over 4 ms
    assert _reader("moe_expert_hbm_pct")(view) == pytest.approx(
        100 * 8 * 16 * 22_020_096 / 819e9 / 4e-3)
    assert _reader("moe_experts_hit_pct")(view) == pytest.approx(100 * 15 / 16)
    assert _reader("moe_held_assignments_pct")(view) == pytest.approx(25.0)
    # what reads WRONG here stays off the cell's list: one q_proj, a router of
    # 16 outputs, no mixing (moe_decode_hbm_pct); a mean over all pairs. And
    # three readers that read RIGHT (above) whose own tests hold their lists
    # to the cells they had (test_latent_walk_live, test_moe_grouped_readers),
    # and one that reads nothing in a quiet window (gen_host_gap_ms, as for
    # kexaone_l5): a benchmark issue's to open
    assert _reader("latent_walk_live_pct")(_view([], [], snaps=({}, {
        _key("arkflow_gen_attn_pages_walked_total", "decode"): 409.0,
        _key("arkflow_gen_attn_table_columns_total", "decode"): 992.0}))) == \
        pytest.approx(100 * 409 / 992)
    for name in ("moe_decode_hbm_pct", "moe_load_max_over_mean", "gen_launch_wake_ms",
                 "latent_walk_live_pct", "moe_grouped_chunks_pct",
                 "moe_expert_ms_per_chunk", "gen_host_gap_ms"):
        entry = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL not in entry["workloads"]


def test_entries_and_files():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == BENCH["workloads"][-1] and cell["chips"] == 1
    assert cell["traffic"] == "longdoc_backlog" and len(cell["why"]) <= 200
    conf = BENCH["configs"][-1]
    assert conf["name"] == cell["config"] == CONFIG["name"]
    assert conf["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    names = [m["name"] for m in BENCH["per_layer"]]
    new = ["mhc_mix_ms_per_step", "mhc_mix_ms_per_chunk", "mhc_moe_decode_hbm_pct"]
    assert [n for n in names if n.startswith("mhc_")] == new
    for name in new:
        entry = BENCH["per_layer"][names.index(name)]
        assert entry["workloads"] == [CELL] and entry["moves"] == "tokens_per_s"
        assert os.path.exists(os.path.join(ROOT, "benchmark/metrics", name + ".py"))
    assert CELL in next(m for m in BENCH["end_to_end"]
                        if m["name"] == "tokens_per_s")["workloads"]
    # every published number of the catalog's row is the file's, but the cuts
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Xing4.0-29B-A4B")
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
    assert CONFIG["source"] == row["source_url"] == conf["source"]
    traffic = json.load(open(os.path.join(ROOT, "benchmark/traffic/longdoc_backlog.json")))
    assert traffic["lengths"] == {"dist": "lognormal", "median": 6144, "sigma": 0.5,
                                  "min": 2048, "max": 15360}
    assert (traffic["batch_rows"], traffic["pool_rows"], traffic["fill_rows"],
            traffic["stratify"], traffic["order"]) == (2, 64, 32, 2, "fixed")


def test_the_cell_rehearses_on_the_cpu():
    import subprocess

    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    for trace in (0, 1):
        proc = subprocess.run(
            [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
             "5300000003", "--seconds", "4", "--trace", str(trace), "--rehearse"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
        assert proc.returncode == 0, proc.stderr[-3000:]
        line = json.loads(proc.stdout.strip().splitlines()[-1])
        assert line["correct"] is True and line["failed"] == 0
        assert line["device"]["platform"] == "cpu"
        listed = {m["name"] for m in BENCH["per_layer" if trace else "end_to_end"]
                  if "workloads" not in m or CELL in m["workloads"]}
        assert set(line["metrics"]) <= listed
