"""``paged_decode.UNSERVED`` is the behaviour: for one small configuration a
row of the table (the per-model test files' own) and every column, turning the
feature on where it is turned on — the server's arguments, the processor's
keys, the model's own entry points — either succeeds or raises exactly
``unserved(cfg, feature)``; and what the server and the processor DECIDE by
the table (the ``disagg`` adapter, ``_fuses``, ``_ahead``, swapper and monitor)
agrees with it. Each kind's processor is built once (``decode_kernel:
gather``: no kernel, no probe) and its servers share its parameters."""

from __future__ import annotations

import asyncio
import dataclasses

import jax
import pytest

from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded
from arkflow_tpu.errors import ConfigError
from arkflow_tpu.models import decoder as dec
from arkflow_tpu.models.paged_decode import (FEATURES, UNSERVED, cache_rows,
                                             fusable, paged_fused_step,
                                             paged_prefill, unserved)
from arkflow_tpu.tpu.serving import GenerationServer
from tests.test_conv_gqa_moe import TINY as CONV
from tests.test_eva_decoder import SIZES as EVA
from tests.test_gdn_gqa_moe import TINY as GDN
from tests.test_gen_run_ahead import PER_HEAD_ROUTED as ROUTED
from tests.test_hetero_gqa_moe import DENSE
from tests.test_hybrid_ssm import TINY as HYBRID
from tests.test_kda_mla_moe import TINY as KDA
from tests.test_mhc_mla_moe import TINY as STREAMS
from tests.test_mla_moe import TINY as LATENT
from tests.test_nemotron_h import TINY as ONE_MIXER
from tests.test_sparse_window_moe import TINY as PATTERN
from tests.test_window_gqa_moe import TINY as WINDOW

#: name -> (model_config, rows it must have — a served model's configuration
#: may have more: the window model is routed too): between them every row
KINDS = {
    "kv": (DENSE, {"kv"}),
    "kv_window": (WINDOW, {"kv", "kv_window"}),
    "latent": (LATENT, {"latent"}),
    "index_window": (PATTERN, {"latent", "index", "window"}),
    "ssm": (HYBRID, {"kv", "ssm"}),
    "conv": (CONV, {"kv", "conv"}),
    "gdn": (GDN, {"kv", "gdn"}),
    "kda": (KDA, {"latent", "kda"}),
    "eva": (EVA, {"eva"}),
    "streams": (STREAMS, {"streams", "latent"}),
    "hetero": ({**DENSE, "head_dim": 16, "v_head_dim": 8}, {"kv", "hetero"}),
    "routed": (ROUTED, {"kv", "routed"}),
    "one_mixer": (ONE_MIXER, {"kv", "ssm", "routed", "one_mixer"}),
    "qk_norm": ({**DENSE, "qk_norm": True}, {"kv", "qk_norm"}),
    "switch": ({**DENSE, "num_experts": 4}, {"kv", "switch"}),
}
#: what every server of this file is built with (eva: a chunk that divides
#: its window of 64, pages that divide it and its 16 summary rows)
SERVER = dict(slots=2, page_size=8, max_seq=96, prefill_chunk=16, eos_id=-1,
              decode_kernel="gather")


def _config(kind: str, **extra) -> dict:
    return {"type": "tpu_generate", "model": "decoder_lm",
            "model_config": KINDS[kind][0], "serving": "continuous",
            "max_input": 64, "max_new_tokens": 32, "slots": 2, "page_size": 8,
            "seq_buckets": [16], "prefill_chunk": 16, "eos_id": -1,
            "decode_kernel": "gather", "seed": 3, **extra}


_BUILT: dict = {}


def _proc(kind: str):
    """The kind's plain continuous processor, built once for the file."""
    if kind not in _BUILT:
        ensure_plugins_loaded()
        _BUILT[kind] = build_component("processor", _config(kind), Resource())
    return _BUILT[kind]


def _server(kind: str, **kw) -> GenerationServer:
    proc = _proc(kind)
    return GenerationServer(proc.params, proc.cfg, **{**SERVER, **kw})


def _mesh():
    from arkflow_tpu.parallel.mesh import MeshSpec, create_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    return create_mesh(MeshSpec(tp=2), devices=jax.devices()[:2])


def _holds(why, build, who: str = ""):
    """``build()`` succeeds where ``why`` is None and raises exactly it
    (behind ``who``) otherwise; returns what it built, or None."""
    if why is None:
        return build()
    with pytest.raises(ConfigError) as e:
        build()
    assert str(e.value) == (f"{who} {why}" if who else why)
    return None


def test_the_kinds_cover_every_row_and_every_cell_names_a_feature():
    seen = set()
    for kind, (sizes, rows) in KINDS.items():
        got = set(cache_rows(dec.DecoderConfig(**sizes)))
        assert rows <= got, (kind, got)
        seen |= got
    assert seen == set(UNSERVED)
    assert all(set(cells) <= set(FEATURES) for cells in UNSERVED.values())


@pytest.mark.parametrize("feature", FEATURES)
@pytest.mark.parametrize("kind", list(KINDS))
def test_a_feature_is_served_or_refused_in_the_table_s_words(kind, feature):
    cfg = dec.DecoderConfig(**KINDS[kind][0])
    why = unserved(cfg, feature)
    if feature == "mesh_tp":
        _holds(why, lambda: _server(kind, mesh=_mesh()))
        if why is not None:  # the processor asks before its host init
            _holds(why, lambda: build_component(
                "processor", _config(kind, mesh={"tp": 2}), Resource()))
    elif feature == "prefix_cache":
        _holds(why, lambda: _server(kind, prefix_cache_pages=8))
    elif feature == "speculation":
        _holds(why, lambda: _server(kind, speculative_tokens=2))
    elif feature == "one_shot_prefill":
        _holds(why, lambda: _server(kind, prefill_chunk=0))
        if why is not None:  # the model's own entry point, before any operand
            _holds(why, lambda: paged_prefill(None, cfg, *[None] * 5))
    elif feature == "kv_push":
        proc = _proc(kind)
        assert (getattr(proc, "disagg", None) is proc) == (why is None)
        if why is not None:
            _holds(why, lambda: asyncio.run(
                proc._server.prefill_export([1, 2, 3], 2)),
                who="prefill_export (kv_push)")
            _holds(why, lambda: asyncio.run(
                proc._server.generate_from_pages({"done": False})),
                who="generate_from_pages (kv_push)")
    elif feature == "batch":
        _holds(why, lambda: dec.init_kv_cache(cfg, 1, 16))
        _holds(why, lambda: build_component(
            "processor", _config(kind, serving="batch"), Resource()))
    elif feature in ("swap", "integrity"):
        block = {"swap": {"canary": {"rows": 2}},
                 "integrity": {"probe_interval": "999s"}}[feature]
        built = _holds(why, lambda: build_component(
            "processor", _config(kind, **{feature: block}), Resource()),
            who=f"tpu_generate: {feature}")
        attached = {"swap": "swapper", "integrity": "integrity"}[feature]
        assert why is not None or getattr(built, attached) is not None
        # without the key: attached by default (swap) exactly where served
        if feature == "swap":
            assert (_proc(kind).swapper is not None) == (why is None)
    elif feature == "fused_chunk":
        assert _proc(kind)._server._fuses == fusable(cfg) == (why is None)
        if why is not None:
            _holds(why, lambda: paged_fused_step(None, cfg, *[None] * 10))
    else:  # run_ahead (no live eos_id) / run_ahead_eos (a live one)
        eos = {"run_ahead": -1, "run_ahead_eos": 2}[feature]
        assert _server(kind, eos_id=eos)._ahead == (why is None)
        # the server's own halves: greedy, depth 2, no speculation
        assert not _server(kind, eos_id=eos, dispatch_depth=1)._ahead
        assert not _server(kind, eos_id=eos, temperature=0.7)._ahead


def test_a_reason_names_the_configuration_s_pools_and_streams():
    window = dec.DecoderConfig(**WINDOW)
    assert "pools kv, kv_window" in unserved(window, "speculation")
    streams = dec.DecoderConfig(**STREAMS)
    assert f"hc_mult {streams.hc_mult} " in unserved(streams, "mesh_tp")
    # the more particular row first: the streams' reason, not the latent pool's
    plain = dataclasses.replace(streams, hc_mult=1, **{
        f.name: f.default for f in dataclasses.fields(streams)
        if f.name.startswith("hc_") and f.name != "hc_mult"})
    assert "no head axis" in unserved(plain, "mesh_tp")


def test_the_table_in_docs_config_md_is_the_code_s():
    """``docs/CONFIG.md`` prints the table, kinds by features in the code's
    order: the same rows, the same columns, "no" exactly where a cell is."""
    import re
    from pathlib import Path

    text = (Path(__file__).resolve().parent.parent / "docs" / "CONFIG.md").read_text()
    lines = text[text.index("| kind \\ feature |"):].splitlines()
    assert re.findall(r"`(\w+)`", lines[0]) == list(FEATURES)
    rows = [line for line in lines[2:2 + len(UNSERVED)]]
    assert [re.match(r"\| `(\w+)` \|", r).group(1) for r in rows] == list(UNSERVED)
    for row, line in zip(UNSERVED, rows):
        cells = [c.strip() for c in line.strip("|").split("|")[1:]]
        assert [c.startswith("no") for c in cells] == [
            f in UNSERVED[row] for f in FEATURES], row
