"""``latent_walk_run_pct`` (PR 54) by hand on a made-up view, what it reads of
a program without the count, and its entry in ``BENCHMARK.json``. The count
itself — the server's, by the kernel's predicate over the table a step
carries — is held in ``tests/test_latent_walk.py`` and
``tests/test_paged_serving.py``. ``python -m pytest benchmark/tests -q``;
outside ``tests/``."""

import importlib.util
import json
import os
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "latent_walk_run_pct"
CELLS = ["kanana2_l6.summarize_backlog", "dots3_l5.summarize_long_backlog",
         "xing4_l10.longdoc_backlog"]
WALKED = "arkflow_gen_attn_pages_walked_total"
IN_RUNS = "arkflow_gen_attn_pages_in_runs_total"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def _read(close, sizes=(("kv_lora_rank", 512),), open_=None):
    spec = importlib.util.spec_from_file_location(
        NAME, os.path.join(ROOT, "benchmark/metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    open_ = open_ or {}

    def counter(name, **lab):
        return sum(v - open_.get(key, 0.0) for key, v in close.items()
                   if key[0] == name and all(dict(key[1]).get(k) == val
                                             for k, val in lab.items()))

    return mod.read(types.SimpleNamespace(sizes=dict(sizes), _close=close,
                                          counter=counter))


def _key(name, kind):
    return (name, (("kind", kind), ("model", "decoder_lm")))


def test_by_hand():
    """``xing4_l10``: 40 decode steps of 32 lanes at 7,100 keys (444 pages:
    55 whole stretches of 8 — 440 pages — and 4 pages past them) and 40
    chunks that end at position 6,143 (384 pages, 48 stretches), every
    stretch a run: 40 x (32 x 440 + 384) of 40 x (32 x 444 + 384) pages."""
    close = {_key(WALKED, "decode"): 40 * 32 * 444.0, _key(IN_RUNS, "decode"): 40 * 32 * 440.0,
             _key(WALKED, "chunk"): 40 * 384.0, _key(IN_RUNS, "chunk"): 40 * 384.0}
    assert _read(close) == pytest.approx(100 * (32 * 440 + 384) / (32 * 444 + 384))
    # the window's increase, not the totals: what the fill walked is not its
    assert _read(close, open_={_key(IN_RUNS, "decode"): 40 * 32 * 440.0}) == \
        pytest.approx(100 * 40 * 384 / (40 * (32 * 444 + 384)))


def test_nothing_to_read():
    """A program that predates the count (the parent: pages walked, none in
    runs REGISTERED) leaves the metric out, as do a window without a step
    and a per-head model; a table without a run reads 0; none raises."""
    assert _read({}) is None
    assert _read({_key(WALKED, "decode"): 90.0}) is None
    assert _read({_key(WALKED, "decode"): 0.0, _key(IN_RUNS, "decode"): 0.0}) is None
    counted = {_key(WALKED, "decode"): 90.0, _key(IN_RUNS, "decode"): 0.0}
    assert _read(counted) == 0.0
    assert _read(counted, sizes=(("num_key_value_heads", 8),)) is None


def test_its_entry():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "tokens_per_s", "workloads": CELLS}
    # appended behind PR 53's last entry: nothing before it moved
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NAME) > names.index("mhc_moe_decode_hbm_pct")
    by_name = {w["name"]: w for w in BENCH["workloads"]}
    ends = {e["name"] for e in BENCH["end_to_end"]
            if "workloads" not in e or set(CELLS) <= set(e["workloads"])}
    assert set(CELLS) <= set(by_name) and "tokens_per_s" in ends
