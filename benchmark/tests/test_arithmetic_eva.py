"""The arithmetic behind ``eva_*_hbm_pct``: ``lib/costs_eva.py`` against the
configuration's own statements (1.631 B parameters as run, 6.49 B as
published, 65.0 MB a slot a layer) and hand counts at EvaByte's widths."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import costs_eva as ce  # noqa: E402

with open(os.path.join(ROOT, "benchmark/configs/evabyte-6.5b-l8.json")) as f:
    CONF = json.load(f)
SIZES = ce.sizes_of(types.SimpleNamespace(sizes=CONF))
W, C = CONF["window_size"], CONF["chunk_size"]


def test_sizes_are_the_published_widths():
    assert SIZES == dict(hidden=4096, layers=8, heads=32, kv_heads=32,
                         head_dim=128, ffn=11008, vocab=320)
    assert ce.sizes_of(types.SimpleNamespace(sizes={"hidden_size": 1})) is None
    assert CONF["reduced"] == ["num_hidden_layers"]
    assert CONF["published"] == {"num_hidden_layers": 32}


@pytest.mark.parametrize("layers,total", [(8, 1.631e9), (32, 6.49e9)])
def test_parameter_count_is_the_configuration_s(layers, total):
    shape = {k: v for k, v in SIZES.items() if k != "layers"}
    n = ce.model_params(layers=layers, pred_heads=8, **shape)
    assert abs(n - total) / total < 2e-3, n
    layer = ce.layer_params(hidden=4096, heads=32, kv_heads=32, head_dim=128,
                            ffn=11008)
    assert layer == 4 * 4096 ** 2 + 3 * 4096 * 11008 + 2 * 32 * 128 + 2 * 4096
    assert abs(layer - 202.4e6) < 0.05e6
    assert n == layers * layer + 320 * 4096 + 8 * 320 * 4096 + 4096


def test_a_row_and_a_slot():
    row = ce.row_bytes(kv_heads=32, head_dim=128)
    assert row == 16384
    # 120 summary pages + 128 window pages of 16 rows: 65.0 MB a layer
    assert abs(248 * 16 * row - 65.0e6) < 0.05e6
    assert abs(20 * 8 * 248 * 16 * row - 10.4e9) < 0.01e9
    assert ce.cached_rows(30000, window=W, chunk=C) == 14 * 128 + 1328
    assert ce.cached_rows(2047, window=W, chunk=C) == 2047
    assert ce.cached_rows(2048, window=W, chunk=C) == 128


def test_a_close_reads_33_5_mb_and_writes_2_1():
    read, written = ce.summarise_bytes(window=W, chunk=C, kv_heads=32, head_dim=128)
    assert abs(read - 33.5e6) < 0.1e6 and abs(written - 2.1e6) < 0.01e6
    assert read == 16 * written
    assert ce.summarise_flops(window=W, kv_heads=32, head_dim=128) == 2048 * 32 * 768


def test_a_decode_step_at_the_mix_s_mean_context():
    """20 lanes at ~10k positions: 4 closed windows (512 summary rows) and
    about half a window: the issue's reckoning, 25.6 MB a lane a layer, 4.1 GB
    of cache beside 3.26 GB of weights, ~55 % of 7.4 GB."""
    rows = 20 * (512 + 1052)
    cache = rows * 8 * 16384
    assert abs(cache - 4.1e9) < 0.05e9
    step = ce.decode_step_bytes(rows=rows, lanes=20, **SIZES)
    weights = step - cache - 20 * 8 * 16384
    assert abs(weights - 3.24e9) < 0.02e9          # head 0 of the eight only
    assert 0.54 < cache / step < 0.57
    attn = ce.attention_bytes(rows=rows, queries=20, layers=8, heads=32,
                              kv_heads=32, head_dim=128)
    assert cache < attn < cache * 1.001
    assert ce.attention_flops(pairs=rows, layers=8, heads=32, head_dim=128) \
        == 8 * rows * 32 * 512


def test_a_close_is_the_smallest_op_that_holds_the_kernel():
    """A recorded shape of a traced chunk: the layer scan's ``while`` holds a
    close loop (gather, kernel, scatter) and a second one that closed two
    rows; a loop that closed nothing holds no kernel and is not counted."""
    ops = [["%while.1 = (...) while(...)", 0.0, 9000.0],
           ["%while.3 = (...) while(...)", 100.0, 300.0],
           ["%fusion.1 = bf16[...] fusion(...)", 110.0, 90.0],
           ["%eva_summarise.7 = (...) custom-call(...)", 210.0, 50.0],
           ["%fusion.2 = bf16[...] fusion(...)", 270.0, 120.0],
           ["%while.3 = (...) while(...)", 1000.0, 500.0],
           ["%eva_summarise.7 = (...) custom-call(...)", 1100.0, 50.0],
           ["%eva_summarise.7 = (...) custom-call(...)", 1300.0, 50.0],
           ["%while.3 = (...) while(...)", 2000.0, 5.0],
           ["%eva_summarise.7 = (...) custom-call(...)", 20000.0, 50.0]]
    view = types.SimpleNamespace(trace={"first_device": {
        "ops": ops, "modules": [["jit__chunk(123)", 0.0, 10000.0]]}})
    total, closes = ce.close_calls(view)
    assert closes == 3 and abs(total - 800e-9) < 1e-12
    assert ce.close_calls(types.SimpleNamespace(trace=None)) is None
    assert ce.close_calls(types.SimpleNamespace(trace={"first_device": {
        "ops": ops[:1], "modules": [["jit__chunk", 0.0, 10000.0]]}})) is None
