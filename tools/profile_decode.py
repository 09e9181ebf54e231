"""Profile the paged decode step: TP scaling, kernel choice, dispatch depth.

``tools/profile_step.py`` decomposes the CLASSIFIER step (dp / device-pool
scaling); this tool does the same for the continuous-batching DECODE step,
which is what ``tpu_generate`` ``serving: continuous`` runs in steady state.

**TP mode** (``--devices N``): builds the real ``GenerationServer`` jitted
decode twice — single-chip and tp=N — on identical pool/slot shapes, times
warm steps, and reports:

- ``decode_step_ms_1chip`` / ``decode_step_ms_tp``: warm median step time
- ``tp_speedup``: t1 / tN (ideal = N — TP splits ONE step's work)
- ``tp_scaling_efficiency``: t1 / (N * tN)  (1.0 = perfect TP scaling)
- ``collective_share_est``: max(0, (tN - t1/N) / tN) — the fraction of the
  sharded step NOT explained by partitioned compute; on a real slice this is
  ICI collective time (psum for wo/w_down, lm_head gather), on a virtual
  host mesh it also absorbs shared-core contention (honest caveat below)
- ``per_chip_duty_cycle_est``: (t1/N) / tN per chip — GSPMD runs all chips
  in lockstep, so the estimate is uniform

**Kernel mode** (``--kernel paged|gather``, PR 13): times the warm decode
step with the dense-gather reference AND the paged flash-attention kernel
on a RAGGED page table (half the slots at full context, half short — the
regime where gather pays for every slot's full table and paged skips), and
drives a short real serve-loop burst at dispatch depth 1 and 2, reporting:

- ``decode_step_ms_gather`` / ``decode_step_ms_paged`` +
  ``paged_vs_gather_speedup`` (>1 = paged wins; the requested ``--kernel``
  is echoed so a CI pin on either kernel stays explicit)
- ``device_idle_gap_ms`` p50/p99 at depth 1 and depth 2 — the
  dispatch-depth win, separately attributable from the kernel win

so both PR-13 scoreboard numbers come from one command, no bench rerun.

    python tools/profile_decode.py --devices 4
    python tools/profile_decode.py --kernel paged
    PROF_SLOTS=16 PROF_CTX=256 PROF_STEPS=32 python tools/profile_decode.py --devices 8

NOTE: virtual host devices share physical cores — efficiency on a laptop is
bounded by cores/N; on a real N-chip slice the same number reads as true TP
scaling. On CPU the paged kernel runs INTERPRETED (functional, not
representative — the speedup line only means something on TPU backends).
``PROF_TINY=0`` profiles the llama3-8b shape (real-TPU use only).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def _cli_devices() -> int:
    if "--devices" in sys.argv:
        return int(sys.argv[sys.argv.index("--devices") + 1])
    return int(os.environ.get("PROF_DEVICES", "2"))


def _cli_kernel():
    if "--kernel" in sys.argv:
        i = sys.argv.index("--kernel") + 1
        if i >= len(sys.argv):
            print("profile_decode: --kernel paged|gather", file=sys.stderr)
            sys.exit(2)
        return sys.argv[i]
    return os.environ.get("PROF_KERNEL")


def _median_ms(fn, reps: int) -> float:
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append((time.perf_counter() - t0) * 1000.0)
    ts.sort()
    return ts[len(ts) // 2]


def _child(n: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from arkflow_tpu.models import get_model
    from arkflow_tpu.parallel.mesh import MeshSpec, create_mesh, shard_params
    from arkflow_tpu.tpu.serving import GenerationServer, pack_operands

    tiny = os.environ.get("PROF_TINY", "1") == "1"
    slots = int(os.environ.get("PROF_SLOTS", "8"))
    ctx = int(os.environ.get("PROF_CTX", "64"))  # context tokens per slot
    page_size = int(os.environ.get("PROF_PAGE", "16"))
    steps = int(os.environ.get("PROF_STEPS", "16"))

    fam = get_model("decoder_lm")
    cfg = fam.make_config(**(
        {"vocab_size": 512, "dim": 64, "layers": 2, "heads": 4, "kv_heads": 2,
         "ffn": 96, "max_seq": max(ctx + page_size, 128)} if tiny else {}))
    params = fam.init(jax.random.PRNGKey(0), cfg)
    print(f"# devices={len(jax.devices())} n={n} slots={slots} ctx={ctx} "
          f"tiny={tiny}", file=sys.stderr, flush=True)

    def build(mesh):
        p = params
        if mesh is not None:
            axes = {name: name for name in mesh.axis_names}
            p = shard_params(params, fam.param_specs(cfg, axes), mesh)
        return GenerationServer(p, cfg, slots=slots, page_size=page_size,
                                max_seq=ctx + page_size, mesh=mesh)

    def measure(srv) -> float:
        # synthetic steady state: every slot active at ctx tokens, pages
        # dense — exactly the shape the serve loop dispatches
        pages_per = -(-ctx // page_size)
        table = np.zeros((slots, srv.pages_per_slot), np.int32)
        for s in range(slots):
            table[s, :pages_per] = np.arange(
                1 + s * pages_per, 1 + (s + 1) * pages_per)
        packed = jnp.asarray(pack_operands(
            np.zeros(slots), np.full(slots, ctx), np.ones(slots), table))
        kp, vp = srv.k_pages, srv.v_pages

        def step():
            nonlocal kp, vp
            nxt, kp, vp = srv._decode(packed, kp, vp, *srv._no_prev)
            jax.block_until_ready(nxt)

        step()  # compile
        return _median_ms(step, steps)

    t1 = measure(build(None))
    mesh = create_mesh(MeshSpec(tp=n), devices=jax.devices()[:n])
    tn = measure(build(mesh))

    ideal = t1 / n
    duty = round(min(1.0, ideal / tn), 4) if tn > 0 else 0.0
    print(json.dumps({
        "devices": n,
        "slots": slots,
        "context_tokens": ctx,
        "steps_measured": steps,
        "decode_step_ms_1chip": round(t1, 3),
        "decode_step_ms_tp": round(tn, 3),
        "tp_speedup": round(t1 / tn, 4) if tn > 0 else 0.0,
        "tp_scaling_efficiency": round(t1 / (n * tn), 4) if tn > 0 else 0.0,
        "collective_share_est": round(max(0.0, (tn - ideal) / tn), 4)
        if tn > 0 else 0.0,
        "per_chip_duty_cycle_est": [duty] * n,
        "host_cores": os.cpu_count(),
        "caveat": "virtual host devices share physical cores; on a real "
                  "slice collective_share_est is ICI time",
    }), flush=True)


def _child_kernel(kernel: str) -> None:
    """Single-device child: gather-vs-paged warm step medians on a ragged
    page table, plus a depth-1-vs-2 serve-loop burst for idle-gap p50/p99."""
    import asyncio

    import jax
    import jax.numpy as jnp
    import numpy as np

    from arkflow_tpu.models import get_model
    from arkflow_tpu.models.paged_decode import paged_decode_step
    from arkflow_tpu.tpu.serving import GenerationServer

    tiny = os.environ.get("PROF_TINY", "1") == "1"
    slots = int(os.environ.get("PROF_SLOTS", "8"))
    ctx = int(os.environ.get("PROF_CTX", "64"))
    page_size = int(os.environ.get("PROF_PAGE", "16"))
    steps = int(os.environ.get("PROF_STEPS", "16"))
    on_tpu = jax.devices()[0].platform == "tpu"

    fam = get_model("decoder_lm")
    cfg = fam.make_config(**(
        {"vocab_size": 512, "dim": 64, "layers": 2, "heads": 4, "kv_heads": 2,
         "ffn": 96, "max_seq": max(ctx + page_size, 128)} if tiny else {}))
    params = fam.init(jax.random.PRNGKey(0), cfg)

    def build(**kw):
        return GenerationServer(params, cfg, slots=slots, page_size=page_size,
                                max_seq=ctx + page_size,
                                kernel_parity_check=False, **kw)

    def measure_kernel(name: str) -> float:
        # RAGGED steady state: even slots at full ctx, odd slots at one page
        # — gather still materializes every slot's full table width, paged
        # stops at each row's causal bound
        srv = build()
        pages_per = -(-ctx // page_size)
        table = np.zeros((slots, srv.pages_per_slot), np.int32)
        lens_host = np.zeros(slots, np.int32)
        for s in range(slots):
            n_pg = pages_per if s % 2 == 0 else 1
            table[s, :n_pg] = np.arange(1 + s * pages_per,
                                        1 + s * pages_per + n_pg)
            lens_host[s] = (ctx if s % 2 == 0 else page_size) - 1
        tok = jnp.zeros((slots,), jnp.int32)
        lens = jnp.asarray(lens_host)
        act = jnp.ones((slots,), bool)
        tbl = jnp.asarray(table)
        kw = dict(attention_kernel=name,
                  kernel_interpret=(name == "paged" and not on_tpu))
        fn = jax.jit(lambda tok, lens, act, tbl, kp, vp: paged_decode_step(
            params, cfg, tok, lens, act, tbl, kp, vp, return_logits=True,
            **kw))
        kp, vp = srv.k_pages, srv.v_pages

        def step():
            nonlocal kp, vp
            lg, kp, vp = fn(tok, lens, act, tbl, kp, vp)
            jax.block_until_ready(lg)

        step()  # compile
        return _median_ms(step, steps)

    t_gather = measure_kernel("gather")
    t_paged = measure_kernel("paged")

    def burst(depth: int):
        srv = build(dispatch_depth=depth,
                    decode_kernel=kernel,
                    kernel_interpret=(kernel == "paged" and not on_tpu))
        gaps: list[float] = []

        class _Rec:
            def observe(self, v):
                gaps.append(float(v))

        prompts = [[3 + s, 17, 42][: 1 + s % 3] for s in range(slots * 2)]

        async def go():
            await srv.generate([5], max_new_tokens=4)  # warm compiles
            gaps.clear()
            await asyncio.gather(*[
                srv.generate(p, max_new_tokens=steps) for p in prompts])
            await srv.close()

        srv.m_idle_gap = _Rec()
        asyncio.run(go())
        gaps.sort()
        pct = (lambda q: round(
            gaps[min(len(gaps) - 1, int(q * len(gaps)))] * 1e3, 3)
            if gaps else 0.0)
        return {"p50": pct(0.5), "p99": pct(0.99)}

    g1, g2 = burst(1), burst(2)
    print(json.dumps({
        "kernel": kernel,
        "slots": slots,
        "context_tokens": ctx,
        "steps_measured": steps,
        "decode_step_ms_gather": round(t_gather, 3),
        "decode_step_ms_paged": round(t_paged, 3),
        "paged_vs_gather_speedup": round(t_gather / t_paged, 4)
        if t_paged > 0 else 0.0,
        "device_idle_gap_ms_depth1": g1,
        "device_idle_gap_ms_depth2": g2,
        "backend": jax.devices()[0].platform,
        "paged_interpreted": not on_tpu,
        "host_cores": os.cpu_count(),
        "caveat": "on CPU the paged kernel runs interpreted — the kernel "
                  "speedup line is only meaningful on TPU backends; the "
                  "idle-gap depth comparison is structural and holds "
                  "everywhere",
    }), flush=True)


def main() -> None:
    kernel = _cli_kernel()
    child = os.environ.get("_ARKFLOW_PROFDEC_CHILD")
    if kernel is not None:
        if kernel not in ("paged", "gather"):
            print("profile_decode: --kernel paged|gather", file=sys.stderr)
            sys.exit(2)
        if child == "kernel":
            _child_kernel(kernel)
            return
    else:
        n = _cli_devices()
        if n < 2:
            print("profile_decode: --devices N (N >= 2) or --kernel "
                  "paged|gather required", file=sys.stderr)
            sys.exit(2)
        if child == "1":
            _child(n)
            return
    # the forced host device count only takes effect pre-import, and a
    # CPU-only child pins the CPU in its own env — always re-exec into a
    # CPU child (same recipe as profile_step host-mesh)
    import subprocess

    from arkflow_tpu.utils.cleanenv import cpu_child_env

    if kernel is not None:
        env = cpu_child_env(n_devices=1)
        env["_ARKFLOW_PROFDEC_CHILD"] = "kernel"
        argv = [sys.executable, os.path.abspath(__file__), "--kernel", kernel]
    else:
        env = cpu_child_env(n_devices=n)
        env["_ARKFLOW_PROFDEC_CHILD"] = "1"
        argv = [sys.executable, os.path.abspath(__file__), "--devices", str(n)]
    res = subprocess.run(argv, env=env, timeout=900)
    sys.exit(res.returncode)


if __name__ == "__main__":
    main()
