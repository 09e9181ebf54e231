"""Hand-worked cases for ``benchmark/lib/costs_mla_moe.py`` (the counts
behind the Kanana-2 cell's roofline shares). ``python -m pytest
benchmark/tests -q``; outside ``tests/``, so no tier-1 count changes."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import costs_mla_moe as c  # noqa: E402

KANANA = dict(hidden=2048, heads=32, nope=128, rope=64, v=128, kv_lora=512)


def test_attention_and_expert_parameters():
    # q_proj 2048 x 6144 = 12,582,912; kv_a 2048 x 576 = 1,179,648;
    # kv_b 512 x 8192 = 4,194,304; o_proj 4096 x 2048 = 8,388,608
    assert c.attention_params(**KANANA) == 26_345_472
    # one routed expert: 3 x 2048 x 768 = 4,718,592 weights = 9.44 MB in bf16
    assert c.expert_params(hidden=2048, width=768) == 4_718_592
    assert c.expert_params(hidden=2048, width=6144) == 37_748_736


def test_experts_hit_by_16_lanes():
    # 128 x (1 - (122/128)^16) = 128 x (1 - 0.46383) = 68.63
    assert c.expected_experts_hit(experts=128, top_k=6, tokens=16) == \
        pytest.approx(68.63, abs=0.01)
    # a 128-token chunk touches all but a quarter of an expert
    assert c.expected_experts_hit(experts=128, top_k=6, tokens=128) == \
        pytest.approx(127.72, abs=0.01)


def test_decode_step_bytes_with_69_experts_hit():
    # head 2048 x 128,256 x 2 B                          =   525,336,576
    # dense layer (26,345,472 + 37,748,736) x 2 B        =   128,188,416
    # 5 expert layers, each: attention 52,690,944 + router 2048 x 128 x 4 B
    #   1,048,576 + (69 + 2) experts x 9,437,184         =   723,779,584
    #                                               x 5  = 3,618,897,920
    # no cache                                     total = 4,272,422,912
    got = c.decode_step_bytes(
        layers=6, dense_layers=1, dense_width=6144, moe_width=768,
        experts=128, shared=2, vocab=128256, experts_hit=69, kv_tokens=0,
        **KANANA)
    assert got == 4_272_422_912        # the issue's "4.3 GB"
    assert got / 819e9 == pytest.approx(5.217e-3, rel=1e-3)
    # 16 lanes at 671 tokens each: 10,736 rows x 6 layers x 576 x 2 B
    with_cache = c.decode_step_bytes(
        layers=6, dense_layers=1, dense_width=6144, moe_width=768,
        experts=128, shared=2, vocab=128256, experts_hit=69,
        kv_tokens=16 * 671, **KANANA)
    assert with_cache - got == 10_736 * 6 * 1152 == 74_207_232
    # reading all 128 experts instead would be 5 x 59 x 9.44 MB more
    every = c.decode_step_bytes(
        layers=6, dense_layers=1, dense_width=6144, moe_width=768,
        experts=128, shared=2, vocab=128256, experts_hit=128, kv_tokens=0,
        **KANANA)
    assert every - got == 5 * 59 * 9_437_184


def test_expert_product_and_latent_attention_bytes():
    # 5 layers x 71 experts x 9,437,184 B
    assert c.expert_product_bytes(hidden=2048, moe_width=768, shared=2,
                                  experts_hit=69, expert_layers=5) == \
        3_350_200_320
    # 16 lanes, 10,736 attended rows: rows 10,736 x 1,152 B = 12,367,872;
    # queries 16 x 32 heads x (576 + 512) x 2 B = 1,114,112; x 6 layers
    assert c.latent_attention_bytes(heads=32, kv_lora=512, rope=64,
                                    kv_tokens=10_736, queries=16, layers=6) == \
        6 * (12_367_872 + 1_114_112)
