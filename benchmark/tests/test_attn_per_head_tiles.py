"""``attn_per_head_tiles_pct`` (PR 43) by hand on a made-up view, its entry
in ``BENCHMARK.json``, and the five cells that list it rehearsed on the CPU:
each cell's rehearsal-size model served through the per-head paged kernel
(interpreted) and the reader applied to the registry's increase. A cell's
``--rehearse`` run serves ``decode_kernel: gather`` (its file's overlay),
which cuts no tiles and counts none: that line leaves the metric out.
``python -m pytest benchmark/tests -q``; outside ``tests/``."""

import importlib.util
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "attn_per_head_tiles_pct"
CELLS = ["mistral_l6.summarize_backlog", "mistral_tp4.summarize_backlog",
         "falconh1_l4.chat_backlog", "kexaone_l5.mixed_backlog",
         "mimo_l7.long_reason_backlog"]
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def _read(counters):
    spec = importlib.util.spec_from_file_location(
        NAME, os.path.join(ROOT, "benchmark/metrics", NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(types.SimpleNamespace(
        counter=lambda name, **lab: sum(
            v for (n, *labels), v in counters.items()
            if n == name and all(f"{k}={val}" in labels for k, val in lab.items()))))


def test_by_hand():
    """A window of 17 chunks of 512 positions over MiMo's seven layers (32
    query tiles of 16 positions a layer) and 45 decode steps of 64 lanes:
    17 x 32 x 7 = 3,808 chunk programs, all a K/V head at a time; the
    20,160 decode programs are all heads at once and are not the reader's."""
    name = "arkflow_gen_attn_tiles_total"
    counters = {(name, "kind=chunk", "product=per_kv_head"): 3808.0,
                (name, "kind=chunk", "product=all_heads"): 0.0,
                (name, "kind=decode", "product=per_kv_head"): 0.0,
                (name, "kind=decode", "product=all_heads"): 45 * 64 * 7.0}
    assert _read(counters) == 100.0
    # a model whose sliding layers' chunk rows are no multiple of a sublane
    # tile: two layers of seven a K/V head at a time
    counters[name, "kind=chunk", "product=per_kv_head"] = 3808.0 * 2 / 7
    counters[name, "kind=chunk", "product=all_heads"] = 3808.0 * 5 / 7
    assert _read(counters) == pytest.approx(100 * 2 / 7)


def test_nothing_to_read():
    """The parent's program (no such counter), a ``gather`` server, a latent
    model and a window without a chunk leave the metric out; none raises."""
    assert _read({}) is None
    name = "arkflow_gen_attn_tiles_total"
    assert _read({(name, "kind=decode", "product=all_heads"): 900.0}) is None


def test_its_entry():
    (entry,) = [m for m in BENCH["per_layer"] if m["name"] == NAME]
    assert entry == BENCH["per_layer"][-1]  # appended, nothing moved
    assert entry == {"name": NAME, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "tokens_per_s", "workloads": CELLS}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(CELLS) <= cells


REHEARSAL = """
import asyncio, importlib.util, json, os, sys
sys.path.insert(0, os.getcwd())
from benchmark import run as br
bench = json.load(open("BENCHMARK.json"))
cell, conf = br.lookup(bench, sys.argv[1])
config = json.load(open(conf["file"]))
eng, sizes = br.build_engine_mapping(config, 3000000019, True)
proc = eng["streams"][0]["pipeline"]["processors"][0]
proc.update(decode_kernel="paged", kernel_interpret=True)
from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded
ensure_plugins_loaded()
server = build_component("processor", proc, Resource())._server
before = br.registry_snapshot()
chunk = int(proc["prefill_chunk"])
prompt = [1 + i %% 200 for i in range(min(2 * chunk + 3, int(proc["max_input"])))]
asyncio.run(server.generate(prompt, 3))
view = br.View.__new__(br.View)
view._open, view._close = before, br.registry_snapshot()
spec = importlib.util.spec_from_file_location("m", "benchmark/metrics/%s.py")
mod = importlib.util.module_from_spec(spec); spec.loader.exec_module(mod)
print(json.dumps({"value": mod.read(view),
                  "decode": view.counter("arkflow_gen_attn_tiles_total", kind="decode",
                                         product="all_heads"),
                  "decode_per_head": view.counter("arkflow_gen_attn_tiles_total",
                                                  kind="decode", product="per_kv_head")}))
""" % NAME


@pytest.mark.parametrize("cell", CELLS)
def test_the_cells_that_list_it_read_100_through_the_kernel(cell):
    """Each listing cell's rehearsal model (its file's overlay), served
    through the paged kernel interpreted: every chunk program a K/V head at
    a time, every decode program all heads at once (tp4: four host devices,
    the shapes one chip sees)."""
    chips = next(w["chips"] for w in BENCH["workloads"] if w["name"] == cell)
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "TF_CPP_MIN_LOG_LEVEL": "2",
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={chips}"}
    proc = subprocess.run([sys.executable, "-c", REHEARSAL, cell], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["value"] == 100.0
    assert got["decode"] > 0 and got["decode_per_head"] == 0
