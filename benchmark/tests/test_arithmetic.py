"""The yardstick's own arithmetic, checked by hand-worked values.

Run with ``python -m pytest benchmark/tests -q`` (outside ``tests/``: these
change no tier-1 count)."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import costs, traffic, xtrace  # noqa: E402
from benchmark.lib.stats import percentile, rate_between_writes  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_bert_base_flops_at_256x512():
    # per token and layer: 4 x 768^2 + 2 x 768 x 3072 + 2 x 512 x 768
    #   = 2,359,296 + 4,718,592 + 786,432 = 7,864,320 multiply-adds
    # x 12 layers x 131,072 tokens = 12,369,505,812,480
    # + 256 rows x (768^2 + 768 x 2) = 151,388,160      -> x 2 operations
    got = costs.bert_forward_flops(hidden=768, layers=12, ffn=3072,
                                   batch=256, seq=512)
    assert got == 2 * (12_369_505_812_480 + 151_388_160)
    # 24.7 TFLOP: 0.1256 s at the v5e's 197 TFLOP/s
    assert got / 197e12 == pytest.approx(0.12558, rel=1e-3)


MISTRAL = dict(dim=4096, heads=32, kv_heads=8, ffn=14336, vocab=32768)


def test_mistral_decode_step_bytes_at_16_slots():
    # per layer: Q and O 2 x 4096 x 4096 = 33,554,432; K and V
    # 2 x 4096 x 1024 = 8,388,608; SwiGLU 3 x 4096 x 14336 = 176,160,768
    #   = 218,103,808 parameters; head 4096 x 32768 = 134,217,728
    assert costs.decoder_weight_params(layers=6, **MISTRAL) == \
        6 * 218_103_808 + 134_217_728
    # 16 slots at 674 tokens of context each: K and V of 8 heads x 128 in
    # bf16 = 4,096 bytes a token a layer
    kv_tokens = 16 * 674
    got = costs.decode_step_bytes(layers=6, kv_tokens=kv_tokens, **MISTRAL)
    want = 2 * (6 * 218_103_808 + 134_217_728) + kv_tokens * 6 * 4096
    assert got == want  # 3.15 GB: 3.85 ms at 819 GB/s
    assert got / 819e9 == pytest.approx(3.847e-3, rel=1e-3)
    # tensor parallel over 4 chips: a quarter each
    assert costs.decode_step_bytes(layers=6, kv_tokens=kv_tokens, chips=4,
                                   **MISTRAL) == want / 4


def test_decode_step_flops_counts_every_lane():
    got = costs.decode_step_flops(layers=6, slots=16, kv_tokens=0, **MISTRAL)
    assert got == 2 * 16 * (6 * 218_103_808 + 134_217_728)


def test_unknown_device_kind_is_an_error():
    assert costs.device_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        costs.device_peaks("TPU v5")


def test_same_multiset_of_lengths_for_every_seed():
    spec = traffic.load_traffic("classify_backlog")
    spec["pool_rows"] = 512
    a = traffic.build_pool(spec, 1)
    b = traffic.build_pool(spec, 3_000_000_019)
    assert sorted(a.tokens) == sorted(b.tokens)
    assert list(a.tokens) != list(b.tokens)
    assert a.texts != b.texts
    # a row's words + [CLS] + [SEP] are its tokens
    assert all(len(t.split()) + 2 == n for t, n in zip(a.texts, a.tokens))
    # the mix as ISSUE 23 states it: median 48, about a third <= 32, ~5% > 256
    full = traffic.lengths_multiset(traffic.load_traffic("classify_backlog")["lengths"], 8192)
    assert 47 <= np.median(full) <= 48
    assert 0.33 <= (full <= 32).mean() <= 0.36
    assert 0.04 <= (full > 256).mean() <= 0.05
    assert full.min() == 3 and full.max() == 510


def test_same_arrivals_in_another_order():
    spec = {"rate_rows_per_s": 700, "pool_rows": 4096}
    a = traffic.arrival_offsets(spec, 1, 20.0)
    b = traffic.arrival_offsets(spec, 2, 20.0)
    # one pool's worth of gaps sums to exactly pool_rows / rate, whatever the
    # seed: the rows offered in a window do not depend on it
    n = 4096
    assert a[n - 1] == pytest.approx(n / 700, rel=1e-9)
    assert b[n - 1] == pytest.approx(n / 700, rel=1e-9)
    ga, gb = np.diff(a[:n]), np.diff(b[:n])
    assert not np.allclose(ga, gb)
    # the rate offered over 15 s, and exponential gaps (cv near 1)
    assert abs((a <= 15.0).sum() - 10_500) <= 60
    assert abs((b <= 15.0).sum() - 10_500) <= 60
    assert 0.9 <= ga.std() / ga.mean() <= 1.1
    # bursts of 8 rows keep the mean rate
    c = traffic.arrival_offsets({**spec, "burst_rows": 8}, 1, 20.0)
    assert abs((c <= 15.0).sum() - 10_500) <= 200
    assert (np.diff(c) == 0).mean() > 0.8


def test_rate_between_writes():
    # writes of 256 rows at t = 9.9, 10.1, 10.6, 11.1, 19.9, 20.2; window
    # [10, 20]: first inside at 10.1, last at 19.9; the work of the writes
    # after the first (3 x 256) over 9.8 s
    writes = [(9.9, 256), (10.1, 256), (10.6, 256), (11.1, 256), (19.9, 256),
              (20.2, 256)]
    assert rate_between_writes(writes, 10.0, 20.0) == pytest.approx(768 / 9.8)
    # moving an edge between two writes changes nothing
    assert rate_between_writes(writes, 9.95, 20.15) == pytest.approx(768 / 9.8)
    assert rate_between_writes(writes[:2], 10.0, 20.0) is None


def test_percentile():
    assert percentile([1, 2, 3, 4, 5], 50) == 3
    assert percentile(list(range(101)), 95) == 95
    assert percentile([], 50) is None


# -- the trace reducer on a small recorded trace -------------------------------


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(DATA, "small_trace.json")) as f:
        return json.load(f)


def test_reducer_on_handmade_trace():
    # device: a while [0,50) holding fusion.1 [0,30) and all-reduce.2 [30,50)
    # (ops nest or are disjoint on a device line), then fusion.1 [100,130);
    # host: X covers the gap [50,100) wholly, Z nests in X, Y overlaps partly
    ms = 1e6
    hlo = "%{} = bf16[8,128]{{1,0:T(8,128)(2,1)}} fusion(bf16[8,128] %p.1), kind=kLoop"
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_step(7)", 0, 50 * ms],
                                               ["jit_step(7)", 100 * ms, 30 * ms]]},
            {"name": "XLA Ops", "events": [[hlo.format("while.3"), 0, 50 * ms],
                                           [hlo.format("fusion.1"), 0, 30 * ms],
                                           [hlo.format("all-reduce.2"), 30 * ms, 20 * ms],
                                           [hlo.format("fusion.1"), 100 * ms, 30 * ms]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [["X(fetch)", 50 * ms, 50 * ms],
                                           ["Z", 55 * ms, 40 * ms],
                                           ["Y", 45 * ms, 25 * ms]]}]}]}
    r = xtrace.reduce_trace(trace)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.130)
    assert r["busy_s"] == pytest.approx(0.080)
    # self time: the loop's own time is what its body does not cover (none)
    assert dict(r["device_ops"]) == pytest.approx(
        {"fusion.1": 0.060, "all-reduce.2": 0.020, "while.3": 0.0})
    assert sum(r["op_totals"].values()) == pytest.approx(r["busy_s"])
    # the gap [50,100): every instant goes to the shortest event over it:
    # Y (25 long) holds [50,70), Z (40) the rest of [55,95), X (50) [95,100)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"Y": 0.020, "Z": 0.025, "X_fetch_": 0.005})
    assert r["modules"] == {"jit_step": [pytest.approx(0.05), pytest.approx(0.03)]}
    dev = r["first_device"]
    total, steps = xtrace.ops_inside(dev["ops"], dev["modules"], "jit_step",
                                     "all-reduce")
    assert (total, steps) == (pytest.approx(0.020), 2)
    # instants no host event covers are the host outside the runtime
    trace["planes"][1]["lines"][0]["events"] = [["Y", 45 * ms, 25 * ms]]
    assert dict(xtrace.reduce_trace(trace)["idle_gaps"]) == pytest.approx(
        {"Y": 0.020, "untraced_host_time": 0.030})


def test_reducer_on_recorded_trace(recorded):
    """The first 46 ms of a real v5e trace of ``mistral_l6.summarize_backlog``
    (PR 23 chip run; HLO text cut to 120 characters): a key split, an
    unstack and decode steps with the host's fetch between them. ``expect``
    holds what the reducer gave when the slice was cut; here the same
    numbers are worked out again by brute force on a 1-microsecond grid."""
    trace, want = recorded["trace"], recorded["expect"]
    r = xtrace.reduce_trace(trace)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert [n for n, _ in r["device_ops"][:5]] == want["top_ops"]
    assert [n for n, _ in r["idle_gaps"][:3]] == want["top_gaps"]
    # brute force: paint the ops on a grid
    dev = next(p for p in trace["planes"] if p["name"].startswith("/device:TPU"))
    ops = next(ln["events"] for ln in dev["lines"] if ln["name"] == "XLA Ops")
    lo = min(e[1] for p in trace["planes"] for ln in p["lines"] for e in ln["events"])
    hi = max(e[1] + e[2] for p in trace["planes"] for ln in p["lines"] for e in ln["events"])
    grid = np.zeros(int((hi - lo) / 1e3) + 2, bool)
    for _, start, dur in ops:
        grid[int((start - lo) / 1e3):int(np.ceil((start + dur - lo) / 1e3))] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=0.02)
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    # self times add up to the busy time; gaps to the idle time
    assert sum(r["op_totals"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    idle = r["window_s"] - r["busy_s"]
    assert sum(v for _, v in r["idle_gaps"]) <= idle * (1 + 1e-6)
    assert sum(v for _, v in r["idle_gaps"]) >= 0.9 * idle
    # the decode program ran, and its name survives the id the profiler adds
    assert any(k == "jit__decode" for k in r["modules"])
    assert 0.020 < np.median(r["modules"]["jit__decode"]) < 0.035


def test_generator_options_for_the_cells_kept_for_later():
    # evenly spaced arrivals with +-20 % jitter: same rate, bounded gaps
    spec = {"rate_rows_per_s": 2.0, "pool_rows": 64, "jitter": 0.2}
    a = traffic.arrival_offsets(spec, 5, 100.0)
    gaps = np.diff(a)
    assert abs(len(a) - 200) <= 2
    assert gaps.min() >= 0.4 - 1e-9 and gaps.max() <= 0.6 + 1e-9
    # a shared preamble: every row starts with the same words after its tag
    spec = {"pool_rows": 8, "shared_prefix_tokens": 20,
            "lengths": {"dist": "uniform", "min": 40, "max": 60}}
    pool = traffic.build_pool(spec, 9)
    heads = {tuple(t.split()[1:21]) for t in pool.texts}
    assert len(heads) == 1 and len({t.split()[0] for t in pool.texts}) == 8
    assert all(40 <= n <= 60 for n in pool.tokens)
    # stratified groups: each group of 4 holds one row of every quartile,
    # and a fixed order is the same for every seed while the words differ
    spec = traffic.load_traffic("summarize_backlog")
    a, b = traffic.build_pool(spec, 1), traffic.build_pool(spec, 2)
    assert list(a.tokens) == list(b.tokens) and a.texts != b.texts
    bands = np.sort(a.tokens).reshape(4, -1)
    for group in a.tokens.reshape(-1, 4):
        assert sorted(np.searchsorted(bands[:, -1], group)) == [0, 1, 2, 3]
    # warm-up rows carry negative ids
    warm = traffic.warmup_pools(spec, 1)
    assert [w.n for w in warm] == [4] and warm[0].id_base == -4
