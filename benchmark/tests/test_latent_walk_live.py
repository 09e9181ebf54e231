"""``latent_walk_live_pct`` (PR 44) by hand on a made-up view, its entry in
``BENCHMARK.json``, and the two cells that list it rehearsed on the CPU: each
cell's rehearsal-size model served through the latent kernel (interpreted)
and the reader applied to the registry's increase. A cell's ``--rehearse``
run serves ``decode_kernel: gather`` (its file's overlay), which walks no
table and counts nothing: that line leaves the metric out.
``python -m pytest benchmark/tests -q``; outside ``tests/``."""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "latent_walk_live_pct"
CELLS = ["kanana2_l6.summarize_backlog", "dots3_l5.summarize_long_backlog"]
WALKED = "arkflow_gen_attn_pages_walked_total"
COLUMNS = "arkflow_gen_attn_table_columns_total"
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def _read(counters, sizes=(("kv_lora_rank", 512),)):
    spec = importlib.util.spec_from_file_location(
        NAME, os.path.join(ROOT, "benchmark/metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(types.SimpleNamespace(
        sizes=dict(sizes),
        counter=lambda name, **lab: sum(
            v for (n, *labels), v in counters.items()
            if n == name and all(f"{k}={val}" in labels for k, val in lab.items()))))


def test_by_hand():
    """``dots3_l5``'s table has 784 columns. A window of 40 decode steps of
    32 lanes — 25 at a context of 4,800 (301 pages), 7 idle (a page each) —
    and 40 chunks of 512 tokens ending at position 3,071 (192 pages): 40 x
    (25 x 301 + 7) + 40 x 192 pages over 40 x 32 x 784 + 40 x 784 columns."""
    counters = {(WALKED, "kind=decode"): 40 * (25 * 301 + 7.0),
                (COLUMNS, "kind=decode"): 40 * 32 * 784.0,
                (WALKED, "kind=chunk"): 40 * 192.0,
                (COLUMNS, "kind=chunk"): 40 * 784.0}
    assert _read(counters) == pytest.approx(100 * 308960 / 1034880)  # 29.85


def test_nothing_to_read():
    """The parent's program (no such count for a latent model), a ``gather``
    server and a window without a step leave the metric out; a per-head
    model's walk counts under the same names and is not this reader's; none
    raises."""
    assert _read({}) is None
    assert _read({(WALKED, "kind=decode"): 0.0, (COLUMNS, "kind=decode"): 0.0}) is None
    per_head = {(WALKED, "kind=decode"): 90.0, (COLUMNS, "kind=decode"): 300.0}
    assert _read(per_head, sizes=(("num_key_value_heads", 8),)) is None
    assert _read(per_head) == 30.0


def test_its_entry():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "tokens_per_s", "workloads": CELLS}
    # appended behind PR 43's entry, which ended the list: nothing moved
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(NAME) == names.index("attn_per_head_tiles_pct") + 1
    by_name = {w["name"]: w for w in BENCH["workloads"]}
    ends = {e["name"] for e in BENCH["end_to_end"]
            if "workloads" not in e or set(CELLS) <= set(e["workloads"])}
    assert set(CELLS) <= set(by_name) and "tokens_per_s" in ends


REHEARSAL = """
import asyncio, importlib.util, json, os, sys
sys.path.insert(0, os.getcwd())
from benchmark import run as br
bench = json.load(open("BENCHMARK.json"))
cell, conf = br.lookup(bench, sys.argv[1])
config = json.load(open(conf["file"]))
eng, sizes = br.build_engine_mapping(config, 3000000019, True)
proc = eng["streams"][0]["pipeline"]["processors"][0]
proc.update(decode_kernel=sys.argv[2], kernel_interpret=True)
from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded
ensure_plugins_loaded()
server = build_component("processor", proc, Resource())._server
before = br.registry_snapshot()
chunk = int(proc["prefill_chunk"])
prompt = [1 + i %% 200 for i in range(min(2 * chunk + 3, int(proc["max_input"])))]
asyncio.run(server.generate(prompt, 3))
view = br.View.__new__(br.View)
view._open, view._close, view.sizes = before, br.registry_snapshot(), sizes
spec = importlib.util.spec_from_file_location("m", "benchmark/metrics/%s.py")
mod = importlib.util.module_from_spec(spec); spec.loader.exec_module(mod)
print(json.dumps({
    "value": mod.read(view), "page": int(proc["page_size"]), "chunk": chunk,
    "prompt": len(prompt), "columns": server.pages_per_slot,
    **{k + "_" + kind: view.counter("arkflow_gen_attn_" + k + "_total", kind=kind)
       for k in ("pages_walked", "table_columns") for kind in ("decode", "chunk")},
    "tiles": view.counter("arkflow_gen_attn_tiles_total")}))
""" % NAME


def _rehearse(cell, kernel):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "2"}
    proc = subprocess.run([sys.executable, "-c", REHEARSAL, cell, kernel], cwd=ROOT,
                          env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_the_cells_that_list_it_read_their_walk_through_the_kernel(cell):
    """Each listing cell's rehearsal model (its file's overlay), served
    through the latent kernel interpreted: the chunks' pages are the hand
    count — chunk i of a prompt ends at position (i + 1) x chunk - 1, padding
    included —, every decode step counts all its lanes' columns, and the
    reader is the two sums' ratio. No per-head tile is counted."""
    got = _rehearse(cell, "paged")
    chunks = -(-got["prompt"] // got["chunk"])
    assert got["pages_walked_chunk"] == sum(
        min(((i + 1) * got["chunk"] - 1) // got["page"] + 1, got["columns"])
        for i in range(chunks))
    assert got["table_columns_chunk"] == chunks * got["columns"]
    assert got["table_columns_decode"] > 0
    assert 0 < got["pages_walked_decode"] < got["table_columns_decode"]
    assert got["value"] == pytest.approx(
        100 * (got["pages_walked_chunk"] + got["pages_walked_decode"])
        / (got["table_columns_chunk"] + got["table_columns_decode"]))
    assert got["tiles"] == 0


def test_a_gather_server_reads_nothing():
    got = _rehearse(CELLS[0], "gather")
    assert got["value"] is None and got["table_columns_decode"] == 0
