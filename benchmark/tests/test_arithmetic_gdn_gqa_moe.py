"""Hand-worked cases for ``benchmark/lib/costs_gdn_gqa_moe.py`` (the counts
behind the Qwen3-Next cell's roofline shares). ``python -m pytest
benchmark/tests -q``; outside ``tests/``, so no tier-1 count changes."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import costs_gdn_gqa_moe as c  # noqa: E402

MIXER = dict(value_heads=32, key_dim=128, value_dim=128)
SIZES = dict(hidden=2048, layers=8, linear_layers=6, heads=16, kv_heads=2,
             head_dim=256, key_heads=16, taps=4, moe_width=512,
             router_outputs=512, shared=1, vocab=18_992, **MIXER)


def test_a_slots_state():
    # 32 heads x 128 x 128 float32 = 2 MiB a layer: as much as 1,024 tokens
    # of the full layers' K/V a layer (2 x 2 x 256 x 2 B = 2,048 B a token)
    assert c.state_bytes(**MIXER) == 2 * 2 ** 20 == 32 * 65_536 == 1024 * 2048
    # the conv's last 3 inputs over q | k | v = 2,048 + 2,048 + 4,096 channels
    assert c.conv_channels(key_heads=16, **MIXER) == 8192
    assert c.window_bytes(conv_channels=8192, taps=4) == 3 * 8192 * 2 == 49_152
    assert c.slot_bytes(linear_layers=6, conv_channels=8192, taps=4, **MIXER) \
        == 6 * (2_097_152 + 49_152) == 12_877_824


def test_update_bytes_of_128_lanes():
    # 128 lanes x 6 layers x (state read + written 4,194,304 + window 49,152)
    got = c.update_bytes(lanes=128, layers=6, conv_channels=8192, taps=4, **MIXER)
    assert got == 768 * 4_243_456 == 3_258_974_208
    # 3.26 GB: 3.98 ms at 819 GB/s
    assert got / 819e9 == pytest.approx(3.979e-3, rel=1e-3)


def test_mixer_parameters():
    # q | k | v | z: 2048 x 12,288 = 25,165,824; b | a: 2048 x 64 = 131,072;
    # taps 8192 x 4 = 32,768; out 4096 x 2048 = 8,388,608        = 33,718,272
    assert c.linear_params(hidden=2048, key_heads=16, taps=4, **MIXER) == 33_718_272
    # queries and gate 2048 x 8192 = 16,777,216; k, v 2 x 2048 x 512 =
    # 2,097,152; o 4096 x 2048 = 8,388,608                       = 27,262,976
    assert c.attention_params(hidden=2048, heads=16, kv_heads=2, head_dim=256) \
        == 27_262_976


def test_expert_bytes_of_a_step():
    # an expert 3 x 2048 x 512 = 3,145,728 parameters = 6,291,456 B; a step
    # that hits 59 of the 64 held a layer reads (59 + 1 shared) x 8 of them
    got = c.expert_bytes(hidden=2048, moe_width=512, experts_hit=59, shared=1,
                         layers=8)
    assert got == 480 * 6_291_456 == 3_019_898_880


def test_decode_step_bytes_of_128_lanes_at_3100_tokens():
    # head 2048 x 18,992 x 2 B                                =    77,791,232
    # mixers (6 x 33,718,272 + 2 x 27,262,976) x 2 B          =   513,671,168
    # routers + shared gates 8 x 2048 x 513 x 4 B             =    33,619,968
    # experts (59 hit + 1 shared) x 8 x 6,291,456             = 3,019,898,880
    # K/V 396,800 tokens x 2 layers x 2,048 B                 = 1,625,292,800
    # states + windows 128 x 6 x 2 x (2,097,152 + 49,152)     = 3,296,722,944
    got = c.decode_step_bytes(experts_hit=59, lanes=128, context=128 * 3100,
                              **SIZES)
    assert got == (77_791_232 + 513_671_168 + 33_619_968 + 3_019_898_880
                   + 1_625_292_800 + 3_296_722_944) == 8_566_996_992
    # the new state's stream is the largest of the step: 38.5 %; 10.5 ms
    assert 3_296_722_944 / got == pytest.approx(0.3848, abs=1e-3)
    assert got / 819e9 == pytest.approx(10.46e-3, rel=1e-3)


def test_chunk_scan_counts_and_the_roof_that_binds():
    shape = dict(tokens=512, layers=6, **MIXER)
    # a token a head, blocks of 64: K K^T and Q K^T causal halves 2 x 64 x 128
    # / 2 = 8,192; the solve 32 x 256 = 8,192; (Q K^T) V' 32 x 128 = 4,096;
    # W S, Q S, K^T V' 3 x 16,384 = 49,152                     = 69,632 MACs
    assert c.chunk_scan_flops(**shape) == 2 * 6 * 512 * 32 * 69_632 \
        == 13_690_208_256
    # a layer: state in and out 4,194,304; a token q, k of 16 key heads
    # 2 x 2,048, v and o 2 x 4,096, two gates of 32: 12,352 values x 4 B
    assert c.chunk_scan_bytes(key_heads=16, **shape) \
        == 6 * (4_194_304 + 512 * 49_408) == 176_947_200
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, binds = c.roofline_seconds(c.chunk_scan_flops(**shape),
                                      c.chunk_scan_bytes(key_heads=16, **shape),
                                      peaks)
    assert binds == "bytes" and least == pytest.approx(0.2161e-3, rel=1e-3)
    assert c.chunk_scan_flops(**shape) / 197e12 == pytest.approx(0.0695e-3, rel=1e-2)


def test_full_layers_attention_bytes():
    from benchmark.lib.costs_conv_gqa_moe import attention_bytes

    # 128 lanes at 3,100 keys: 396,800 keys x 2,048 B a layer, queries in and
    # outputs back 128 x 2 x 16 x 256 x 2 B = 2,097,152, two layers
    got = attention_bytes(kv_heads=2, head_dim=256, heads=16, keys=128 * 3100,
                          queries=128, layers=2)
    assert got == 2 * (396_800 * 2048 + 2_097_152) == 1_629_487_104


def test_sizes_are_read_under_the_source_s_keys():
    import json
    import types

    with open(os.path.join(ROOT, "benchmark/configs/qwen3-next-80b-a3b-l8-ep8.json")) as f:
        sizes = json.load(f)
    assert c.sizes_of(types.SimpleNamespace(sizes=sizes)) == SIZES
    assert c.sizes_of(types.SimpleNamespace(sizes={"num_hidden_layers": 4})) is None
