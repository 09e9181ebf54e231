#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the streaming-inference main path once, through the entry points a
user calls (config file -> ``EngineConfig`` -> ``Engine`` -> ``build_stream``,
the same code ``python -m arkflow_tpu --config`` runs), in ONE process that
holds the chip, and checks what comes out against plain references:

- classify: ``examples/chip_smoke_classify.yaml`` — BERT-base at full width
  (bf16) behind the memory buffer's bucket-exact coalescing, served in two
  (batch, seq) buckets, a SQL aggregate, a collecting sink. Rows out account
  for rows in, every batch is acked, the seq >= 128 bucket traced the ragged
  Pallas kernel, and labels/scores agree with a plain ``fam.apply`` on
  float32 master weights (XLA attention, no runner).
- generate: ``examples/chip_smoke_generate.yaml`` — continuous batching at
  Llama-3-8B head geometry with the auto-selected decode kernel, against
  the same config served by ``decode_kernel: gather`` and a teacher-forced
  plain forward.

Random-weight models produce near-tied outputs, so equality is asked only
where the reference decides: a label where the reference's score clears
0.5 by the tolerance, a token where the reference's top-2 logit margin
exceeds twice the tolerance (``tpu/serving_core.logits_parity``'s rule).

``--chips 4`` runs ONLY the multi-chip paths and what they are compared
with: ``mesh: {dp: 4}`` classify and ``mesh: {tp: 4}`` generate against
their one-device runs.

Every earlier stdout line is one JSON object per phase; timings in them are
smoke timings, not results. The last line is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
Any failed check, a device that is not a TPU, or a native tier that fell
back to Python ends the run non-zero with no such line.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import gc
import json
import os
import sys
import time
from typing import NamedTuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CLASSIFY_YAML = os.path.join(HERE, "examples", "chip_smoke_classify.yaml")
GENERATE_YAML = os.path.join(HERE, "examples", "chip_smoke_generate.yaml")

#: one bfloat16 machine epsilon — two bf16 steps for scores, which live in
#: [0.5, 1). Logits are held to ``tpu/serving_core.bf16_logit_tolerance``
SCORE_TOL = 2.0 ** -7

_WORDS = ("latency error timeout nominal retry queue shard replica commit "
          "offset sensor reading drift alert cleared gateway deploy rollback "
          "cache miss hit ratio budget window tenant quota burst drain "
          "checkpoint restore warm cold page fault kernel trace span").split()


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- seeded traffic -----------------------------------------------------------


def _sentence(rng, n_words: int) -> str:
    return " ".join(_WORDS[i] for i in rng.integers(0, len(_WORDS), n_words))


def classify_payloads(seed: int, rows: int, distinct: int = 64) -> list[str]:
    """``rows`` ragged texts: the first half short (<= 30 tokens -> seq 32),
    the second half long (33..126 tokens -> seq 128), each half cycling
    ``distinct`` different texts — so the bucket-exact windows the buffer
    carves land in both seq buckets and the SQL stage really aggregates."""
    rng = np.random.default_rng(seed)
    short = [f"s{i} " + _sentence(rng, int(rng.integers(2, 28)))
             for i in range(distinct)]
    long_ = [f"l{i} " + _sentence(rng, int(rng.integers(32, 124)))
             for i in range(distinct)]
    half = rows // 2
    return ([short[i % distinct] for i in range(half)]
            + [long_[i % distinct] for i in range(rows - half)])


def generate_prompts(seed: int, page: int, chunk: int, max_input: int,
                     n: int) -> list[str]:
    """``n`` prompts whose token counts (words + [CLS]/[SEP]) straddle page
    boundaries — one a single page, several mid-page past a boundary, one
    longer than ``prefill_chunk`` (chunked prefill)."""
    rng = np.random.default_rng(seed + 1)
    tokens = [page - 3, page + 1, 2 * page + 5, 3 * page + 1, 5 * page + 2,
              chunk - 1, chunk + page + 3, max_input - 5]
    tokens = [min(t, max_input - 2) for t in tokens][:n]
    return [f"p{i} " + _sentence(rng, t - 3) for i, t in enumerate(tokens)]


# -- smoke-only plugins: count acks going in, collect batches coming out -------


class Group(NamedTuple):
    """One row of the SQL aggregate: a text's rows within one window."""

    label: int
    n: int
    mean: float
    lo: float
    hi: float


class Tally:
    def __init__(self):
        self.reads = self.rows_in = self.acks = self.nacks = 0
        self.batches: list = []


def register_smoke_plugins() -> None:
    """Two smoke-only component types, registered once: an input wrapper
    that counts reads and acks, and a sink that keeps what it is written.
    Each takes the run's ``Tally`` through its config mapping."""
    from arkflow_tpu.components import (Ack, Input, Output, build_component,
                                        register_input, register_output)
    from arkflow_tpu.components.registry import registered_types

    if "chip_smoke_counted" in registered_types("input"):
        return

    class CountedAck(Ack):
        def __init__(self, inner, tally):
            self._inner, self._tally = inner, tally

        async def ack(self):
            self._tally.acks += 1
            await self._inner.ack()

        async def nack(self):
            self._tally.nacks += 1
            await self._inner.nack()

    class CountedInput(Input):
        def __init__(self, inner, tally):
            self._inner, self._tally = inner, tally

        async def connect(self):
            await self._inner.connect()

        async def read(self):
            batch, ack = await self._inner.read()
            self._tally.reads += 1
            self._tally.rows_in += batch.num_rows
            return batch, CountedAck(ack, self._tally)

        async def close(self):
            await self._inner.close()

    class CollectOutput(Output):
        def __init__(self, tally):
            self._tally = tally

        async def connect(self):
            return None

        async def write(self, batch):
            self._tally.batches.append(batch)

    @register_input("chip_smoke_counted")
    def _counted(config, resource):
        return CountedInput(build_component("input", config["inner"], resource),
                            config["tally"])

    @register_output("chip_smoke_collect")
    def _collect(config, resource):
        return CollectOutput(config["tally"])


def run_engine(cfg, tally: Tally):
    """What ``arkflow_tpu.runtime.cli.main`` does after parsing the config —
    logging, Engine, run to end of input — with the counted input and the
    collecting sink swapped in. Returns the finished stream."""
    from arkflow_tpu.runtime.cli import init_logging
    from arkflow_tpu.runtime.engine import Engine

    register_smoke_plugins()
    for s in cfg.streams:
        s.input = {"type": "chip_smoke_counted", "inner": dict(s.input),
                   "tally": tally}
        s.output = {"type": "chip_smoke_collect", "tally": tally}
    init_logging(cfg.logging)
    engine = Engine(cfg)
    asyncio.run(engine.run())
    require(len(engine.streams) == 1, "engine built no stream")
    stream = engine.streams[0]
    # the engine logs a crashed stream and returns: hold it to its counters
    for name in ("m_errors", "m_write_errors", "m_quarantined",
                 "m_ack_failures"):
        require(getattr(stream, name).value == 0,
                f"stream counter {name} = {getattr(stream, name).value}")
    require(tally.reads > 0 and tally.acks == tally.reads and tally.nacks == 0,
            f"acks {tally.acks} / nacks {tally.nacks} of {tally.reads} reads")
    return stream


def load_config(path: str):
    from arkflow_tpu.config import EngineConfig

    return EngineConfig.from_file(path)


def device_bytes() -> list[int]:
    import jax

    return [int((d.memory_stats() or {}).get("bytes_in_use", -1))
            for d in jax.devices()]


def leaf_devices(tree) -> list[str]:
    import jax

    devs = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        devs |= {str(d) for d in leaf.sharding.device_set}
    return sorted(devs)


def placed_param_bytes() -> dict:
    """dtype -> bytes of the generate tree as placed: the program's
    ``arkflow_gen_param_bytes`` gauge, set by ``tpu_generate`` at placement."""
    from arkflow_tpu.obs import global_registry

    return {m.labels["dtype"]: int(m.value)
            for m in global_registry().collect()
            if getattr(m, "name", "") == "arkflow_gen_param_bytes"}


# -- classify ------------------------------------------------------------------


def attention_paths(runner) -> dict:
    """Which attention each served (batch, seq) bucket traced, read off the
    lowered step itself: a Pallas kernel lowers to ``tpu_custom_call``."""
    import jax
    import jax.numpy as jnp

    out = {}
    for key, dispatches in sorted(runner.dispatch_counts().items()):
        shapes = dict(key)
        args = {k: jax.ShapeDtypeStruct(v, jnp.int32) for k, v in shapes.items()}
        with runner.mesh or contextlib.nullcontext():
            text = runner._jitted.lower(runner.params, args).as_text()
        b, s = shapes["input_ids"]
        out[f"{b}x{s}"] = {
            "attention": ("ragged_pallas" if "tpu_custom_call" in text
                          else "xla"),
            "dispatches": dispatches}
    return out


def classify_reference(proc_cfg: dict, texts: list[str]) -> dict:
    """Plain float32-master ``fam.apply`` (XLA attention, no runner) over the
    distinct texts, each padded to the smallest configured seq bucket that
    holds it. Returns text -> (label, score)."""
    import jax

    from arkflow_tpu.models import get_model
    from arkflow_tpu.tpu.runner import init_host_params
    from arkflow_tpu.tpu.tokenizer import build_tokenizer

    fam = get_model(proc_cfg["model"])
    cfg = fam.make_config(**{**(proc_cfg.get("model_config") or {}),
                             "use_flash_attention": False})
    params = jax.device_put(
        init_host_params(fam, cfg, int(proc_cfg.get("seed", 0))),
        jax.devices()[0])
    tok = build_tokenizer(proc_cfg.get("tokenizer"), vocab_size=cfg.vocab_size)
    ids, mask = tok.encode_batch([t.encode() for t in texts],
                                 int(proc_cfg["max_seq"]))
    seq_of = [min(b for b in proc_cfg["seq_buckets"] if b >= n)
              for n in mask.sum(axis=1)]
    apply = jax.jit(lambda p, i, m: fam.apply(p, cfg, input_ids=i,
                                              attention_mask=m))
    ref = {}
    for sb in sorted(set(seq_of)):
        rows = [i for i, b in enumerate(seq_of) if b == sb]
        out = jax.device_get(apply(params, ids[rows, :sb], mask[rows, :sb]))
        for j, i in enumerate(rows):
            ref[texts[i]] = (int(out["label"][j]), float(out["score"][j]))
    return ref


def classify_phase(seed: int, overrides: dict, tag: str) -> dict:
    """One engine run of the classify config; returns per-text served
    results after checking them against the plain reference."""
    t0 = time.perf_counter()
    cfg = load_config(CLASSIFY_YAML)
    s = cfg.streams[0]
    configured = int(s.input["count"])
    s.input["payloads"] = classify_payloads(seed, int(s.input["batch_size"]))
    proc = s.pipeline.processors[0]
    proc.update(overrides.get("processor", {}))
    s.buffer["coalesce"].update(overrides.get("coalesce", {}))
    tally = Tally()
    stream = run_engine(cfg, tally)
    runner = stream.pipeline.processors[0].runner
    wall = time.perf_counter() - t0

    served: dict[str, list[Group]] = {}
    rows_out = 0
    for batch in tally.batches:
        d = batch.to_pydict()
        for text, label, n, mean, lo, hi in zip(
                d["text"], d["label"], d["n"], d["mean_score"],
                d["min_score"], d["max_score"]):
            text = text.decode() if isinstance(text, bytes) else text
            served.setdefault(text, []).append(
                Group(int(label), int(n), float(mean), float(lo), float(hi)))
            rows_out += int(n)
    require(rows_out == tally.rows_in == configured,
            f"rows: {tally.rows_in} in, {rows_out} accounted for by the "
            f"aggregate, {configured} configured")

    # auto-selection announces: the ragged kernel from seq 128 up on one
    # TPU device, XLA attention below that and under a mesh
    paths = attention_paths(runner)
    want = "xla" if runner.mesh is not None else "ragged_pallas"
    for shape, info in paths.items():
        seq = int(shape.split("x")[1])
        require(info["attention"] == (want if seq >= 128 else "xla"),
                f"bucket {shape} traced {info['attention']}")
    require(len({k.split("x")[1] for k in paths}) >= 2
            and any(int(k.split("x")[1]) >= 128 for k in paths),
            f"served buckets {sorted(paths)}: need two seq buckets, one >= 128")

    ref = classify_reference(proc, sorted(served))
    decided = flips = 0
    worst = 0.0
    for text, groups in served.items():
        ref_label, ref_score = ref[text]
        worst = max([worst] + [abs(x - ref_score) for g in groups
                               for x in (g.lo, g.hi)])
        if ref_score - 0.5 > SCORE_TOL:
            decided += 1
            flips += int({g.label for g in groups} != {ref_label})
    require(np.isfinite(worst) and worst <= SCORE_TOL,
            f"served score off the float32 reference by {worst} > {SCORE_TOL}")
    require(flips == 0 and decided > 0,
            f"{flips} label flips on {decided} decided texts")
    emit(tag, config=os.path.relpath(CLASSIFY_YAML, HERE),
         model="bert_classifier (BERT-base: hidden %d, layers %d, heads %d, "
               "ffn %d)" % (runner.cfg.hidden, runner.cfg.layers,
                            runner.cfg.heads, runner.cfg.ffn),
         serving_dtype=runner.serving_dtype,
         mesh=overrides.get("processor", {}).get("mesh"),
         params_on=leaf_devices(runner.params),
         rows_in=tally.rows_in, rows_accounted=rows_out,
         reads=tally.reads, acks=tally.acks, batches_out=len(tally.batches),
         buckets=paths, distinct_texts=len(served), decided_texts=decided,
         label_flips_on_decided=flips, max_abs_score_diff=worst,
         score_tol=SCORE_TOL, device_bytes_in_use=device_bytes(),
         smoke_wall_s=round(wall, 2))
    return {"served": served, "runner": runner}


# -- generate ------------------------------------------------------------------


def generate_run(seed: int, overrides: dict):
    cfg = load_config(GENERATE_YAML)
    s = cfg.streams[0]
    proc = s.pipeline.processors[0]
    proc.update(overrides)
    n = int(s.input["batch_size"])
    prompts = generate_prompts(seed, int(proc["page_size"]),
                               int(proc["prefill_chunk"]),
                               int(proc["max_input"]), n)
    s.input["payloads"] = prompts
    tally = Tally()
    stream = run_engine(cfg, tally)
    processor = stream.pipeline.processors[0]
    tokens = {}
    for batch in tally.batches:
        d = batch.to_pydict()
        for text, gen in zip(d["__value__"], d[proc["output_field"]]):
            text = text.decode() if isinstance(text, bytes) else text
            tokens[text] = [int(t) for t in gen.split()]
    require(sorted(tokens) == sorted(prompts),
            f"{len(tokens)} of {len(prompts)} prompts came back")
    return processor, prompts, tokens, dict(proc)


def teacher_forced_reference(processor, prompts, tokens, max_input: int):
    """Plain ``decoder.forward`` (no paging, XLA attention) over prompt +
    served tokens: logits that predict each generated position."""
    import jax
    import jax.numpy as jnp

    from arkflow_tpu.models.decoder import forward

    cfg = processor.cfg
    ids, mask = processor.tokenizer.encode_batch(
        [p.encode() for p in prompts], max_input)
    plens = mask.sum(axis=1).astype(int)
    width = int(max(plens[i] + len(tokens[p]) for i, p in enumerate(prompts)))
    width = -(-width // 64) * 64
    full = np.zeros((len(prompts), width), np.int32)
    for i, p in enumerate(prompts):
        full[i, :plens[i]] = ids[i, :plens[i]]
        full[i, plens[i]:plens[i] + len(tokens[p])] = tokens[p]
    with processor.mesh or contextlib.nullcontext():
        logits = jax.jit(lambda prm, x: forward(prm, cfg, x))(
            processor.params, jnp.asarray(full))
    logits = np.asarray(jax.device_get(logits))
    return {p: logits[i, plens[i] - 1: plens[i] - 1 + len(tokens[p])]
            for i, p in enumerate(prompts)}, plens


def judge_tokens(prompts, served, ref_logits, other=None) -> dict:
    """Near-tie rule: walking each prompt's tokens, the served token (and
    ``other``'s, where given) must equal the reference argmax wherever the
    reference's top-2 margin exceeds twice the bf16 logit tolerance; the
    walk of a prompt ends where the two runs part ways at a near-tie, after
    which their continuations legitimately differ."""
    from arkflow_tpu.tpu.serving_core import bf16_logit_tolerance

    tol = max(bf16_logit_tolerance(v) for v in ref_logits.values())
    checked = decided = ties = 0
    for p in prompts:
        ref = ref_logits[p]
        top2 = np.partition(ref, -2, axis=-1)[:, -2:]
        margin = top2[:, 1] - top2[:, 0]
        for i, tok in enumerate(served[p]):
            want = int(ref[i].argmax())
            got = {tok, other[p][i]} if other else {tok}
            checked += 1
            if margin[i] > 2 * tol:
                decided += 1
                require(got == {want},
                        f"prompt {p[:12]!r} step {i}: tokens {sorted(got)} vs "
                        f"reference {want} at margin {margin[i]:.4f} > {2 * tol:.4f}")
            elif got != {want}:
                ties += 1
                if len(got) > 1:  # the two runs part ways here
                    break
    require(decided > 0, "no generated position was decided by the reference")
    return {"positions_checked": checked, "positions_decided": decided,
            "near_tie_divergences": ties, "logit_tol": tol}


def generate_phase(seed: int, base: dict, against: dict, tag: str,
                   expect_kernel: str) -> None:
    """Serve the generate config with ``base`` overrides, again with
    ``against`` on top, and hold both to the teacher-forced reference."""
    t0 = time.perf_counter()
    processor, prompts, served, proc_cfg = generate_run(seed, base)
    server = processor.runner
    announced = server.health_report()["decode_kernel"]
    require(announced == expect_kernel == server.decode_kernel,
            f"decode kernel {announced!r} served, expected {expect_kernel!r}")
    ref_logits, plens = teacher_forced_reference(
        processor, prompts, served, int(proc_cfg["max_input"]))
    info = {
        "config": os.path.relpath(GENERATE_YAML, HERE),
        "model_config": proc_cfg["model_config"],
        "cuts": "Llama-3-8B widths; depth 32 -> %d layers, vocabulary 128256 "
                "-> %d" % (processor.cfg.layers, processor.cfg.vocab_size),
        "mesh": base.get("mesh"), "decode_kernel": announced,
        "kernel_parity_probe": server.kernel_parity,
        "params_on": leaf_devices(processor.params),
        "param_bytes_placed": placed_param_bytes(),
        "kv_pool_shape": list(server.k_pages.shape),
        "kv_pool_shard_shapes": sorted(
            {str(tuple(sh.data.shape)) for sh in server.k_pages.addressable_shards}),
        "prompt_tokens": [int(n) for n in plens],
        "page_size": server.page_size, "prefill_chunk": server.prefill_chunk,
        "steps_compiled": sorted(":".join(map(str, k))
                                 for k in server._seen_steps),
        "device_bytes_in_use": device_bytes(),
    }
    placed = info["param_bytes_placed"]
    require(placed.get("bfloat16", 0) > 100 * placed.get("float32", 0),
            f"generate weights are not placed in bfloat16: {placed}")
    require(max(plens) > server.prefill_chunk > 0
            and ("chunk", server.prefill_chunk) in server._seen_steps,
            "no prompt went through chunked prefill")
    wall_a = time.perf_counter() - t0

    del processor, server
    gc.collect()
    t1 = time.perf_counter()
    other_proc, _, other, _ = generate_run(seed, {**base, **against})
    require(other_proc.runner.decode_kernel
            == against.get("decode_kernel", expect_kernel),
            "the comparison run did not serve the kernel asked for")
    verdict = judge_tokens(prompts, served, ref_logits, other)
    equal = sum(served[p] == other[p] for p in prompts)
    emit(tag, **info, compared_with=against,
         prompts=len(prompts), tokens_per_prompt=len(served[prompts[0]]),
         prompts_token_identical=equal, **verdict,
         smoke_wall_s=[round(wall_a, 2), round(time.perf_counter() - t1, 2)])
    del other_proc
    gc.collect()


def compare_classify(one: dict, four: dict) -> None:
    """dp-sharded dispatch against the one-device run of the same batches:
    every text both runs served, labels equal wherever both runs agree with
    themselves (a text served under two labels is a near-tie by
    ``classify_phase``'s reference check), scores within tolerance."""
    worst = 0.0
    compared = 0
    for text, groups in one["served"].items():
        others = four["served"][text]
        a = {g.label for g in groups}
        b = {g.label for g in others}
        if (len(a) == 1 and len(b) == 1
                and min(g.lo for g in groups + others) - 0.5 > SCORE_TOL):
            compared += 1
            require(a == b, f"dp4 label {b} vs one-device {a} on {text[:16]!r}")
        worst = max(worst, abs(groups[0].mean - others[0].mean))
    require(worst <= SCORE_TOL and compared > 0,
            f"dp4 scores off the one-device run by {worst}")
    runner = four["runner"]
    shape = next(iter(runner.dispatch_counts()))
    args = {k: np.zeros(v, np.int32) for k, v in dict(shape).items()}
    out = runner._dispatch(runner._to_device(args))
    shards = {str(s.device): list(s.data.shape)
              for s in out["label"].addressable_shards}
    require(len(shards) == 4 and len(leaf_devices(runner.params)) == 4,
            f"dp4 output shards on {sorted(shards)}")
    emit("classify_dp4_vs_1chip", texts_compared=compared,
         max_abs_mean_score_diff=worst, score_tol=SCORE_TOL,
         input_bucket=dict(shape)["input_ids"],
         output_sharding=str(out["label"].sharding),
         output_shard_shapes=shards,
         params_on=leaf_devices(runner.params),
         device_bytes_in_use=device_bytes())


# -- main ----------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the dp/tp mesh paths and their "
                         "one-device comparisons")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the payloads and prompts")
    args = ap.parse_args(argv)

    import jax

    from arkflow_tpu import native
    from arkflow_tpu.tpu.jaxcache import cache_info, enable_persistent_cache

    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu":
        print(f"chip_smoke: found no TPU (jax reports {dev}); this script "
              "proves the chip path and does not fall back to the CPU",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"jax reports {len(devices)}", file=sys.stderr)
        return 1
    enable_persistent_cache()
    cache0 = cache_info()
    emit("env", device=dev, jax=jax.__version__, chips=args.chips,
         seed=args.seed, native_tier_active=native.available(),
         compile_cache=cache0,
         compile_cache_placed_by=("JAX_COMPILATION_CACHE_DIR"
                                  if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                                  else "fixed default path"))
    try:
        require(native.available(),
                "the native tier fell back to Python (g++ build failed?)")
        if args.chips == 1:
            classify_phase(args.seed, {}, "classify")
            generate_phase(args.seed, {}, {"decode_kernel": "gather"},
                           "generate", expect_kernel="paged")
        else:
            one = classify_phase(args.seed, {}, "classify_1chip")
            # per-chip buckets a quarter of the one-device ones: the global
            # (dp-scaled) emissions and steps are the same 256-row batches
            four = classify_phase(
                args.seed,
                {"processor": {"mesh": {"dp": 4}, "batch_buckets": [16, 64]},
                 "coalesce": {"dp": 4, "batch_buckets": [64]}}, "classify_dp4")
            compare_classify(one, four)
            generate_phase(args.seed, {"mesh": {"tp": 4}}, {"mesh": None},
                           "generate_tp4_vs_tp1", expect_kernel="paged")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    cache1 = cache_info()
    emit("done", compile_cache_entries_before=cache0.get("entries"),
         compile_cache_entries_after=cache1.get("entries"),
         compile_cache_dir=cache1.get("dir"),
         device_bytes_in_use=device_bytes())
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
