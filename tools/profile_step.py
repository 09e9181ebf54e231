"""Decompose the serving step at bench shapes: where do the milliseconds go?

Times, independently, on the current backend (meant for a real TPU):
  1. raw jitted forward (ModelRunner._dispatch + block) at (batch, seq)
  2. host prep (pad/validate, no device work)
  3. tokenizer encode_batch for `batch` strings
  4. a reference MXU matmul with the same analytic FLOPs as the forward

(1) vs (4) separates XLA-inefficiency from physics; (2)+(3) vs (1) says
whether the host pipeline can keep the device fed (with 2 steps in flight,
host time < device time means the device never starves).

    python tools/profile_step.py            # BERT-base bf16 b1024 s32
    PROF_BATCH=256 PROF_SEQ=128 PROF_DTYPE=int8 python tools/profile_step.py

``--devices N`` (or PROF_DEVICES=N) switches to host-mesh mode: the tool
re-execs itself onto a forced N-device virtual CPU platform, serves the same
batch stream through a 1-member and an N-member replicated device pool
(tpu/pool.py), and prints per-chip duty cycle + scaling efficiency
(rows/s at N / (N x rows/s at 1)). Host-mesh mode defaults to the tiny
classifier (PROF_TINY=0 for BERT-base — slow on CPU); PROF_STEPS bounds the
measured steps per phase.

``--per-layer`` (or PROF_PER_LAYER=1) profiles the model LAYER BY LAYER via
the family's pp stage functions and emits per-layer median costs as JSON —
the input of the pipelined-segmentation stage planner
(``parallel/segment.py``; wire the artifact to ``tpu_inference.pp_profile``
or paste ``per_layer_ms`` into ``pp_layer_costs``). PROF_MODEL picks the
family (default bert_classifier), PROF_TINY=1 the CPU-sized config.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def _median_ms(fn, reps: int = 20) -> float:
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1000.0)
    ts.sort()
    return ts[len(ts) // 2]


def _cli_devices() -> int:
    if "--devices" in sys.argv:
        return int(sys.argv[sys.argv.index("--devices") + 1])
    return int(os.environ.get("PROF_DEVICES", "0"))


def _main_per_layer() -> None:
    """--per-layer: per-layer median costs for the pp stage planner.

    Times each layer INDEPENDENTLY through the family's ``pp_stage_fns``
    layer body (the exact math a pipeline stage runs), plus the embed and
    head ends, so the planner balances what the executor will actually
    execute. One executable serves every layer (homogeneous stacks share
    shapes); heterogeneous families would get per-layer executables and
    genuinely different medians — either way the numbers are measured, not
    assumed."""
    import jax
    import numpy as np

    from arkflow_tpu.models import get_model
    from arkflow_tpu.tpu.jaxcache import enable_persistent_cache

    enable_persistent_cache()
    tiny = os.environ.get("PROF_TINY", "0") == "1"
    model = os.environ.get("PROF_MODEL", "bert_classifier")
    fam = get_model(model)
    extras = fam.extras or {}
    if "pp_stage_fns" not in extras:
        print(f"profile_step: model {model!r} has no pp_stage_fns "
              "(per-layer profiling follows pp serving support)",
              file=sys.stderr)
        sys.exit(2)
    model_config = (
        {"vocab_size": 512, "hidden": 32, "layers": 2, "heads": 4,
         "ffn": 64, "max_positions": 64, "num_labels": 2}
        if tiny and model == "bert_classifier" else {})
    cfg = fam.make_config(**model_config)
    batch = int(os.environ.get("PROF_BATCH", "64" if tiny else "1024"))
    seq = int(os.environ.get("PROF_SEQ", "32"))
    reps = int(os.environ.get("PROF_REPS", "10"))
    dev = jax.devices()[0]
    print(f"# per-layer: device={dev} model={model} batch={batch} seq={seq}",
          file=sys.stderr, flush=True)

    params = fam.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    inputs = {}
    for name, (dtype, trailing) in fam.input_spec(cfg).items():
        dims = tuple(seq if d == "seq" else d for d in trailing)
        if name == "input_ids":
            inputs[name] = rng.randint(
                1, cfg.vocab_size, (batch, *dims)).astype(dtype)
        else:
            inputs[name] = np.ones((batch, *dims), dtype)

    pre, layer, post = extras["pp_stage_fns"](cfg)
    pre_j = jax.jit(pre)
    layer_j = jax.jit(layer)
    post_j = jax.jit(post)

    x, aux = pre_j(params, inputs)
    jax.block_until_ready(x)
    t_embed = _median_ms(lambda: jax.device_get(pre_j(params, inputs)[0]),
                         reps=reps)

    n_layers = int(jax.tree_util.tree_leaves(params["layers"])[0].shape[0])
    per_layer = []
    for i in range(n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
        jax.device_get(layer_j(lp, x, aux))  # compile (first layer only)
        per_layer.append(round(_median_ms(
            lambda: jax.device_get(layer_j(lp, x, aux)), reps=reps), 4))

    jax.device_get(post_j(params, x, aux))
    t_head = _median_ms(
        lambda: jax.device_get(post_j(params, x, aux)), reps=reps)

    print(json.dumps({
        "model": model,
        "batch": batch,
        "seq": seq,
        "layers": n_layers,
        "per_layer_ms": per_layer,
        "embed_ms": round(t_embed, 4),
        "head_ms": round(t_head, 4),
        "host_cores": os.cpu_count(),
    }), flush=True)


def _main_multichip(n: int) -> None:
    """Host-mesh mode: per-chip duty cycle + scaling efficiency at N devices."""
    import subprocess

    if os.environ.get("_ARKFLOW_PROF_CHILD") != "1":
        # the forced host device count only takes effect pre-import, and a
        # CPU-only child pins the CPU in its own env — always re-exec into
        # an N-device CPU child (same recipe as dryrun_multichip)
        from arkflow_tpu.utils.cleanenv import cpu_child_env

        env = cpu_child_env(n_devices=n)
        env["_ARKFLOW_PROF_CHILD"] = "1"
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--devices", str(n)],
            env=env, timeout=900)
        sys.exit(res.returncode)

    import asyncio

    import jax
    import numpy as np

    from arkflow_tpu.tpu.bucketing import BucketPolicy
    from arkflow_tpu.tpu.pool import ModelRunnerPool

    tiny = os.environ.get("PROF_TINY", "1") == "1"
    model_config = (
        {"vocab_size": 512, "hidden": 32, "layers": 2, "heads": 4,
         "ffn": 64, "max_positions": 64, "num_labels": 2} if tiny else {})
    batch = int(os.environ.get("PROF_BATCH", "64"))
    seq = int(os.environ.get("PROF_SEQ", "32"))
    steps = int(os.environ.get("PROF_STEPS", "16"))
    print(f"# host-mesh: devices={len(jax.devices())} n={n} batch={batch} "
          f"seq={seq} tiny={tiny}", file=sys.stderr, flush=True)

    pool = ModelRunnerPool(
        "bert_classifier", model_config, pool_size=n,
        buckets=BucketPolicy((batch,), (seq,)))
    pool.warmup()
    rng = np.random.RandomState(0)
    inputs = {
        "input_ids": rng.randint(1, 500 if tiny else 30000,
                                 (batch, seq)).astype(np.int32),
        "attention_mask": np.ones((batch, seq), np.int32),
    }

    def busy_stall():
        return [(m.m_busy_s.value, m.m_stall_s.value) for m in pool.members]

    async def drive(infer, k: int) -> float:
        t0 = time.perf_counter()
        await asyncio.gather(*[infer(inputs) for _ in range(k)])
        return time.perf_counter() - t0

    # phase 1: one member only (its in-flight semaphore still pipelines)
    t1 = asyncio.run(drive(pool.members[0].infer, steps))
    bs0 = busy_stall()
    tn = asyncio.run(drive(pool.infer, steps * n))
    bs1 = busy_stall()

    r1 = steps * batch / t1 if t1 > 0 else 0.0
    rn = steps * n * batch / tn if tn > 0 else 0.0
    duty = []
    for (b0, s0), (b1, s1) in zip(bs0, bs1):
        d_busy, d_stall = b1 - b0, s1 - s0
        duty.append(round(d_busy / (d_busy + d_stall), 4)
                    if d_busy + d_stall > 0 else 0.0)
    print(json.dumps({
        "devices": n,
        "batch": batch,
        "seq": seq,
        "steps_per_phase": steps,
        "rows_per_sec_1chip": round(r1, 1),
        "rows_per_sec_nchip": round(rn, 1),
        "scaling_efficiency": round(rn / (n * r1), 4) if r1 > 0 else 0.0,
        "per_chip_duty_cycle": duty,
        "dispatch_per_chip": [int(c.value) for c in pool.m_dispatch],
        "host_cores": os.cpu_count(),
    }), flush=True)


def main() -> None:
    if "--per-layer" in sys.argv or os.environ.get("PROF_PER_LAYER") == "1":
        _main_per_layer()
        return
    n_devices = _cli_devices()
    if n_devices > 1:
        _main_multichip(n_devices)
        return

    import jax
    import jax.numpy as jnp
    import numpy as np

    from arkflow_tpu.tpu.bucketing import BucketPolicy
    from arkflow_tpu.tpu.jaxcache import enable_persistent_cache
    from arkflow_tpu.tpu.runner import ModelRunner
    from arkflow_tpu.tpu.tokenizer import build_tokenizer

    enable_persistent_cache()
    batch = int(os.environ.get("PROF_BATCH", "1024"))
    seq = int(os.environ.get("PROF_SEQ", "32"))
    dtype = os.environ.get("PROF_DTYPE", "bfloat16")
    dev = jax.devices()[0]
    print(f"# device: {dev} batch={batch} seq={seq} dtype={dtype}",
          file=sys.stderr, flush=True)

    runner = ModelRunner(
        "bert_classifier", {},
        buckets=BucketPolicy((batch,), (seq,)),
        serving_dtype=dtype,
    )
    rng = np.random.RandomState(0)
    ids = rng.randint(1, 30000, (batch, seq)).astype(np.int32)
    mask = np.ones((batch, seq), np.int32)
    inputs = {"input_ids": ids, "attention_mask": mask}

    # per-call round-trip floor: a no-compute dispatch+sync — where it is
    # large it dominates single-step timings, and
    # ceil((rtt+compute)/compute) is the in-flight depth that hides it
    tiny = jax.jit(lambda x: x + 1.0)
    jax.device_get(tiny(jnp.float32(0)))
    t_rtt = _median_ms(lambda: jax.device_get(tiny(jnp.float32(0))))

    padded, _ = runner._prep(inputs)
    # sync via device_get: it forces the wait AND the host copy, which is
    # what the serving path does per step
    jax.device_get(runner._dispatch(padded))  # compile

    t_step = _median_ms(lambda: jax.device_get(runner._dispatch(padded)))
    t_prep = _median_ms(lambda: runner._prep(inputs))

    tok = build_tokenizer(None, vocab_size=30522)
    texts = ["stream processing on tpu: sensor reading nominal"] * batch
    t_tok = _median_ms(lambda: tok.encode_batch(texts, seq), reps=10)

    # reference matmul at the forward's analytic FLOPs: per-layer GEMMs are
    # [b*s, h] @ [h, h] shaped; scale rep count so total FLOPs match.
    # Same formula as bench.py::_bert_flops_per_row (keeps the quadratic
    # attention term, which dominates scaling at long seq)
    h, ffn, layers = 768, 3072, 12
    per_token = 8 * h * h + 4 * h * ffn + 4 * seq * h
    flops_fwd = float(batch * seq * layers * per_token)
    a = jnp.asarray(rng.randn(batch * seq, h), jnp.bfloat16)
    w = jnp.asarray(rng.randn(h, h), jnp.bfloat16)
    n_mm = max(1, int(round(flops_fwd / (2.0 * batch * seq * h * h))))

    @jax.jit
    def mm_chain(a, w):
        def body(x, _):
            return jnp.dot(x, w), None
        out, _ = jax.lax.scan(body, a, None, length=n_mm)
        # scalar output: the device_get sync transfers 4 bytes, so the
        # timing is the GEMM chain, not a 50MB outfeed
        return out.astype(jnp.float32).sum()

    jax.device_get(mm_chain(a, w))
    t_mm = _median_ms(lambda: jax.device_get(mm_chain(a, w)))

    compute = max(t_step - t_rtt, 1e-3)
    print(json.dumps({
        "batch": batch, "seq": seq, "dtype": dtype,
        "roundtrip_floor_ms": round(t_rtt, 3),
        "device_step_ms": round(t_step, 3),
        "device_compute_est_ms": round(compute, 3),
        "host_prep_ms": round(t_prep, 3),
        "tokenize_ms": round(t_tok, 3),
        "ref_matmul_same_flops_ms": round(t_mm, 3),
        "ref_matmul_compute_est_ms": round(max(t_mm - t_rtt, 1e-3), 3),
        "n_ref_matmuls": n_mm,
        "step_vs_matmul": (round((t_step - t_rtt) / (t_mm - t_rtt), 2)
                           if t_mm - t_rtt > 1e-3 else None),
        "host_total_ms": round(t_prep + t_tok, 3),
        "host_can_feed_device": (t_prep + t_tok) < t_step,
        "inflight_to_hide_rtt": int(-(-t_step // compute)),
    }), flush=True)


if __name__ == "__main__":
    main()
