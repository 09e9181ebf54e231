"""Hand-worked cases for ``benchmark/lib/costs_hybrid_ssm.py`` (the counts
behind the Falcon-H1 cell's roofline shares). ``python -m pytest
benchmark/tests -q``; outside ``tests/``, so no tier-1 count changes."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import costs_hybrid_ssm as c  # noqa: E402

MIXER = dict(d_ssm=4096, groups=2, d_state=256, d_conv=4)
LAYER = dict(hidden=5120, heads=20, kv_heads=4, head_dim=128, ffn=21504,
             mixer_heads=32)


def test_a_slots_state():
    # 32 heads x 128 x 256 float32 = 4 MiB a layer: as much as 2,048 tokens
    # of K/V (2 x 4 x 128 x 2 B = 2,048 B a token a layer)
    assert c.state_bytes(d_ssm=4096, d_state=256) == 4 * 2 ** 20 == 2048 * 2048
    # the conv's last 3 inputs over x | B | C = 4096 + 2 x 2 x 256 = 5,120 channels
    assert c.conv_channels(d_ssm=4096, groups=2, d_state=256) == 5120
    assert c.conv_window_bytes(**MIXER) == 3 * 5120 * 2 == 30_720
    assert c.slot_bytes(layers=4, **MIXER) == 4 * (4_194_304 + 30_720) == 16_900_096


def test_update_bytes_of_128_lanes():
    # 128 lanes x 4 layers x (state read + written 8,388,608 + window 30,720)
    assert c.ssm_update_bytes(lanes=128, layers=4, **MIXER) == 512 * 8_419_328
    # 4.31 GB: 5.3 ms at 819 GB/s
    assert c.ssm_update_bytes(lanes=128, layers=4, **MIXER) / 819e9 == \
        pytest.approx(5.263e-3, rel=1e-3)


def test_layer_parameters():
    # attention 5120 x 128 x (2 x 20 + 2 x 4) = 31,457,280
    # mixer: in_proj 5120 x (4096 + 5120 + 32) = 47,349,760; conv 5120 x 4 =
    #   20,480; out_proj 4096 x 5120 = 20,971,520            = 68,341,760
    # SwiGLU 3 x 5120 x 21,504                               = 330,301,440
    assert c.layer_params(**LAYER, **MIXER) == 430_100_480


def test_decode_step_bytes_of_128_lanes_at_400_tokens():
    # weights (4 x 430,100,480 + head 5120 x 261,120) x 2 B  = 6,114,672,640
    # state of 128 lanes                                     = 4,310,695,936
    # K/V 51,200 tokens x 4 layers x 2,048 B                 =   419,430,400
    got = c.decode_step_bytes(lanes=128, kv_tokens=128 * 400, layers=4,
                              vocab=261_120, **LAYER, **MIXER)
    assert got == 10_844_798_976
    # the state is the largest stream of the step: 39.7 %
    assert c.ssm_update_bytes(lanes=128, layers=4, **MIXER) / got == \
        pytest.approx(0.3975, abs=1e-3)


def test_chunk_scan_counts_and_the_roof_that_binds():
    shape = dict(layers=4, d_ssm=4096, groups=2, d_state=256)
    # a token: C B^T 128 x 256 x 2 groups = 65,536; the masked product
    # 128 x 4096 = 524,288; the carried state's output and the update
    # 2 x 4096 x 256 = 2,097,152 multiply-adds
    flops = c.chunk_scan_flops(tokens=256, block=128, **shape)
    assert flops == 2 * 4 * 256 * 2_686_976
    # a layer: state in and out 8,388,608 + 256 tokens x 4 B x (2 x 4096 +
    # 32 + 2 x 2 x 256) = 9,469,952
    nbytes = c.chunk_scan_bytes(tokens=256, mixer_heads=32, **shape)
    assert nbytes == 4 * 17_858_560
    least, binds = c.roofline_seconds(
        flops, nbytes, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert binds == "bytes" and least == pytest.approx(87.2e-6, rel=1e-2)
    # a prompt's one block: the whole chunk is the block
    assert c.chunk_scan_flops(tokens=32, block=128, **shape) == \
        2 * 4 * 32 * (32 * 256 * 2 + 32 * 4096 + 2 * 4096 * 256)
