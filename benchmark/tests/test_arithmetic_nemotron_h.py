"""Hand-worked cases for ``benchmark/lib/costs_nemotron_h.py`` (the counts
behind the ``nh_*`` readers of the Nemotron-3-Nano cell) and for what those
readers return on a view they can and cannot read. ``python -m pytest
benchmark/tests -q``; outside ``tests/``, so no tier-1 count changes."""

import importlib.util
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import costs_nemotron_h as c  # noqa: E402

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
MIXER = dict(d_ssm=4096, groups=8, d_state=128, d_conv=4)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
SIZES = {"hybrid_override_pattern": PATTERN, "num_hidden_layers": 13,
         "hidden_size": 2688, "vocab_size": 65536, "num_attention_heads": 32,
         "num_key_value_heads": 2, "head_dim": 128, "mamba_num_heads": 64,
         "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
         "conv_kernel": 4, "chunk_size": 128, "n_routed_experts": 64,
         "router_outputs": 128, "experts_held": [0, 64], "n_shared_experts": 1,
         "moe_intermediate_size": 1856,
         "moe_shared_expert_intermediate_size": 3712}


def test_the_cut_s_kinds_and_the_whole_model_s():
    assert c.kinds(PATTERN, 13) == {"mamba": 6, "moe": 5, "attention": 2}
    assert c.kinds(PATTERN, 52) == {"mamba": 23, "moe": 23, "attention": 6}


def test_a_slots_state_over_the_mamba_layers_only():
    # 64 heads x 64 x 128 float32 = 2 MiB a layer; the conv's last 3 inputs
    # over 4,096 + 2 x 8 x 128 = 6,144 channels, bf16 = 36,864 B
    assert c.state_bytes(d_ssm=4096, d_state=128) == 2 * 2 ** 20
    assert c.conv_window_bytes(**MIXER) == 3 * 6144 * 2 == 36_864
    assert c.slot_bytes(mamba_layers=6, **MIXER) == 6 * (2_097_152 + 36_864) \
        == 12_804_096
    # as much a slot as 6,252 tokens of the two attention layers' K/V
    assert 12_804_096 // 2048 == 6252


def test_update_bytes_of_192_lanes():
    # 192 lanes x 6 layers x (state read + written 4,194,304 + window 36,864)
    assert c.update_bytes(lanes=192, mamba_layers=6, **MIXER) == 1152 * 4_231_168 \
        == 4_874_305_536
    # 4.87 GB: 5.95 ms at 819 GB/s
    assert 4_874_305_536 / 819e9 == pytest.approx(5.951e-3, rel=1e-3)


def test_expert_bytes_as_hit():
    # an expert 2 x 2,688 x 1,856 = 9,977,856 parameters; the shared one twice
    assert c.expert_params(hidden=2688, width=1856) == 9_977_856
    # all 64 held hit on 5 layers: 5 x (64 + 2) x 9,977,856 x 2 B = 6.585 GB
    got = c.expert_bytes(hidden=2688, moe_width=1856, shared_width=3712,
                         experts_hit=64, moe_layers=5)
    assert got == 5 * 66 * 9_977_856 * 2 == 6_585_384_960


def test_block_parameters():
    # in_proj 2,688 x (4,096 + 6,144 + 64) = 27,697,152; conv 6,144 x (4 + 1)
    # = 30,720; out_proj 4,096 x 2,688 = 11,010,048
    assert c.mamba_params(hidden=2688, mixer_heads=64, **MIXER) == 38_737_920
    # q 2,688 x 4,096, k and v 2,688 x 256 each, o 4,096 x 2,688
    assert c.attention_params(hidden=2688, heads=32, kv_heads=2, head_dim=128) \
        == 2688 * 128 * 68 == 23_396_352


def test_decode_step_bytes_of_192_lanes_at_1300_tokens():
    view = types.SimpleNamespace(sizes=SIZES)
    s = c.sizes_of(view)
    assert (s["mamba_layers"], s["moe_layers"], s["attention_layers"]) == (6, 5, 2)
    assert (s["shared_width"], s["router_outputs"]) == (3712, 128)
    got = c.decode_step_bytes(lanes=192, kv_tokens=192 * 1300, experts_hit=64, **s)
    # mixers 6 x 38,737,920 + 2 x 23,396,352 = 279,220,224; head 2,688 x
    # 65,536 = 176,160,768: x 2 B                            =   910,761,984
    # routers 5 x 2,688 x 128 x 4 B                          =     6,881,280
    # experts                                                = 6,585,384,960
    # states                                                 = 4,874,305,536
    # K/V 249,600 tokens x 2 layers x 1,024 B                =   511,180,800
    assert got == 910_761_984 + 6_881_280 + 6_585_384_960 + 4_874_305_536 \
        + 511_180_800 == 12_888_514_560
    # 15.7 ms at 819 GB/s; the experts 51 %, the states 38 % of it
    assert got / 819e9 == pytest.approx(15.74e-3, rel=1e-3)
    assert 6_585_384_960 / got == pytest.approx(0.511, abs=1e-3)
    assert 4_874_305_536 / got == pytest.approx(0.378, abs=1e-3)


def test_scan_counts_and_the_roof_that_binds():
    flops, nbytes = c.scan_counts(tokens=512, block=128, mamba_layers=6,
                                  d_ssm=4096, groups=8, d_state=128, mixer_heads=64)
    # a token: C B^T 128 x 128 x 8 groups = 131,072; the masked product 128 x
    # 4,096 = 524,288; the state's two parts 2 x 4,096 x 128 = 1,048,576
    assert flops == 2.0 * 6 * 512 * (131_072 + 524_288 + 1_048_576) == 10_468_982_784
    # a layer: the state in and out 4,194,304; a token 4 x (8,192 + 64 + 2,048)
    assert nbytes == 6 * (4_194_304 + 512 * 41_216) == 151_781_376
    least, binds = c.roofline_seconds(flops, nbytes, PEAKS)
    assert binds == "bytes" and least == pytest.approx(185.3e-6, rel=1e-3)


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


READERS = ("nh_update_hbm_pct", "nh_scan_roofline_pct", "nh_expert_ms_per_step",
           "nh_expert_hbm_pct", "nh_experts_hit_pct", "nh_grouped_chunks_pct",
           "nh_decode_hbm_pct", "nh_state_share_of_cache_pct")


class _View:
    """A view of one traced window, by hand: 100 decode steps of 15.9 ms
    with 192 lanes, 40 chunks, every held expert hit."""

    def __init__(self, sizes, ops=True):
        self.sizes, self.peaks = sizes, PEAKS
        self.proc_cfg = {"prefill_chunk": 512, "max_new_tokens": 512}
        self.run = types.SimpleNamespace(pool=types.SimpleNamespace(
            tokens=types.SimpleNamespace(mean=lambda: 1044.0)))
        kern = [("ssm_state_update", 6.6e-3), ("moe_expert_relu2_grouped", 8.2e-3)]
        chunk = [("ssm_chunk_scan", 0.9e-3), ("moe_expert_relu2_grouped", 9.0e-3)]
        mods, ops_, t = [], [], 0.0
        for name, dur, inside in [("jit__decode", 15.9e-3, kern)] * 100 + [
                ("jit__chunk", 14e-3, chunk)] * 40:     # the trace's: in ns
            mods.append((name, t, dur * 1e9))
            at = t
            for op, d in inside if ops else []:
                ops_.append((op, at, d * 1e9))
                at += d * 1e9
            t += dur * 1e9 + 1e5
        self.trace = {"modules": {"jit__decode": [15.9e-3] * 100,
                                  "jit__chunk": [14e-3] * 40},
                      "first_device": {"ops": ops_, "modules": mods}}
        self._open = {("arkflow_gen_kv_live_bytes", (("pool", "kv"),)): 5e8,
                      ("arkflow_gen_kv_live_bytes", (("pool", "ssm"),)): 2.4e9}
        self._close = {**self._open,
                       ("arkflow_gen_moe_grouped_products_total",
                        (("kind", "decode"),)): 500.0}

    def counter(self, name, **labels):
        return {"arkflow_gen_ssm_tokens_total": 19_200.0,
                "arkflow_gen_decode_steps_total": 100.0,
                "arkflow_gen_moe_grouped_products_total":
                    500.0 if labels.get("kind") == "decode" else 700.0}.get(name, 0.0)

    def hist(self, name, **labels):
        if name != "arkflow_gen_moe_experts_hit":
            return 0.0, 0.0
        kind = labels.get("kind")
        return {"decode": (6400.0, 100.0), "chunk": (2560.0, 40.0)}.get(
            kind, (8960.0, 140.0))

    def gauge(self, name):
        return [192.0] * 10 if name == "arkflow_gen_slots_busy" else []


def test_readers_on_a_view_by_hand():
    try:
        from benchmark.lib.xtrace import ops_inside  # noqa: F401
    except Exception:
        pytest.skip("the trace reader is not importable here")
    view = _View(SIZES)
    got = {name: _reader(name)(view) for name in READERS}
    assert got["nh_experts_hit_pct"] == pytest.approx(100.0)
    assert got["nh_grouped_chunks_pct"] == pytest.approx(100.0)
    assert got["nh_state_share_of_cache_pct"] == pytest.approx(100 * 2.4 / 2.9)
    assert got["nh_expert_ms_per_step"] == pytest.approx(8.2, rel=1e-6)
    # 4.874 GB / 819 GB/s = 5.95 ms of the 6.6 the kernel took
    assert got["nh_update_hbm_pct"] == pytest.approx(100 * 5.951 / 6.6, rel=1e-3)
    # 6.585 GB / 819 GB/s = 8.04 ms of the 8.2
    assert got["nh_expert_hbm_pct"] == pytest.approx(100 * 8.041 / 8.2, rel=1e-3)
    assert got["nh_scan_roofline_pct"] == pytest.approx(100 * 0.1853 / 0.9, rel=1e-3)
    # lanes 192 x (1,044 + 256) tokens: the hand-worked 12.89 GB over 15.9 ms
    assert got["nh_decode_hbm_pct"] == pytest.approx(100 * 15.74 / 15.9, rel=1e-3)
    assert all(v is None or v <= 100.0 for v in got.values())


def test_readers_find_nothing_on_another_configuration():
    """A cell of another configuration (no ``hybrid_override_pattern``) and
    a program without the kernels: nothing is returned, nothing is raised."""
    other = _View({"num_hidden_layers": 4, "hidden_size": 5120, "mamba_d_ssm": 4096})
    for name in READERS:
        if name == "nh_expert_ms_per_step":
            continue   # a kernel's time: whatever program runs that kernel
        assert _reader(name)(other) is None, name
    bare = _View(SIZES, ops=False)
    bare.trace = None
    for name in ("nh_update_hbm_pct", "nh_scan_roofline_pct",
                 "nh_expert_ms_per_step", "nh_expert_hbm_pct", "nh_decode_hbm_pct"):
        assert _reader(name)(bare) is None, name
