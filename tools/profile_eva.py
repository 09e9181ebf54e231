"""Time the chunk summariser and an EVA attention call alone, at the shapes ``evabyte_l8.rawlog_backlog`` serves.

What ``eva_summarise_hbm_pct`` and ``eva_attn_hbm_pct`` are checked
against: the kernels alone, on the chip this process holds, one JSON line a
point.

- ``summarise``: ONE close of one layer — a window's 2,048 rows of 32 K/V
  heads x 128, K and V, pooled to 128 summary rows
  (``ops/eva_summarise``) — by the kernel and by its plain form, from dense
  rows (the gather of the window's pages and the scatter of the summary
  pages are not in it). ``roof_pct``: the bytes that had to move
  (``lib/costs_eva.summarise_bytes``: 33.5 MB read, 2.1 MB written) over
  819 GB/s and the call's time.
- ``close``: the whole close of one row of one layer through the pools
  (``paged_decode._eva_close``: gather + kernel + scatter), what
  ``eva_summarise_ms_per_close`` reads in a trace.
- ``attention``: a decode step's attention call of one layer — 20 lanes, a
  query each, through ``paged_flash_attention`` over a table whose columns
  are a lane's summary pages, then its open window's — at 1 to 15 closed
  windows and a window ``--fill`` full. ``roof_pct``: the rows attended x
  16,384 B over 819 GB/s and the call's time.

``us_per_call``: best of ``--rounds`` timed loops of ``--reps`` calls inside
ONE jitted program (the host's dispatch is paid once a loop). Needs a TPU;
``--interpret`` runs tiny shapes on the CPU to rehearse the control flow (its
lines say ``"rehearsal"``).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def best_us(fn, args, reps: int, rounds: int) -> float:
    import jax

    def loop(*a):
        def body(i, acc):
            out = fn(i, acc, *a)
            return acc + sum(o.astype("float32").sum() for o in
                             (out if isinstance(out, (tuple, list)) else (out,)))
        return jax.lax.fori_loop(0, reps, body, 0.0)

    timed = jax.jit(loop)
    jax.block_until_ready(timed(*args))
    best = float("inf")
    for _ in range(rounds):
        t = time.perf_counter()
        jax.block_until_ready(timed(*args))
        best = min(best, time.perf_counter() - t)
    return best / reps * 1e6


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--lanes", type=int, default=20)
    ap.add_argument("--windows", default="1,4,8,15")
    ap.add_argument("--fill", type=float, default=0.5)
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from arkflow_tpu.ops.eva_summarise import eva_summarise, eva_summarise_plain
    from arkflow_tpu.ops.ragged_attention import paged_flash_attention
    from benchmark.lib.costs import device_peaks
    from benchmark.lib.costs_eva import row_bytes, summarise_bytes

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.interpret:
        print("profile_eva: found no TPU (pass --interpret to rehearse)",
              file=sys.stderr)
        return 1
    tiny = args.interpret
    w, c, h, d, page = (64, 4, 2, 128, 8) if tiny else (2048, 16, 32, 128, 16)
    peak = 819e9 if tiny else device_peaks(dev.device_kind)["hbm_bytes_per_s"]
    note = {"rehearsal": True} if tiny else {"device": dev.device_kind}
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    rand = lambda shape: jax.random.normal(  # noqa: E731
        next(keys), shape, jnp.float32).astype(jnp.bfloat16)

    k, v = rand((1, w, h, d)), rand((1, w, h, d))
    phi, mu = (0.5 * jax.random.normal(next(keys), (h, d)) for _ in range(2))
    moved = sum(summarise_bytes(window=w, chunk=c, kv_heads=h, head_dim=d))
    # ``phi`` takes the loop's carry (times zero, which XLA cannot fold for a
    # float): a call an iteration, nothing hoisted out of the timed loop
    for form, fn in (
            ("kernel", lambda i, acc, k, v: eva_summarise(
                k, v, phi + 0.0 * acc, mu, chunk=c, interpret=tiny)),
            ("plain", lambda i, acc, k, v: eva_summarise_plain(
                k, v, phi + 0.0 * acc, mu, c))):
        us = best_us(fn, (k, v), args.reps, args.rounds)
        print(json.dumps({"point": "summarise", "form": form, "us_per_call": us,
                          "moved_mb": moved / 1e6,
                          "roof_pct": 100 * moved / peak / (us * 1e-6), **note}),
              flush=True)

    # the whole close of one row of one layer, through the pools: gather of
    # the window's pages, the kernel, scatter of the summary pages
    from arkflow_tpu.models import decoder as dec
    from arkflow_tpu.models import paged_decode as pd

    cfg = dec.DecoderConfig(
        vocab_size=320, dim=h * d, layers=1, heads=h, kv_heads=h, ffn=64,
        max_seq=4 * w, attention_class="eva", window_size=w, chunk_size=c)
    wp = w // page
    kp, vp = rand((1, 1 + 2 * wp, page, h, d)), rand((1, 1 + 2 * wp, page, h, d))
    tab = (1 + jnp.arange(2 * wp, dtype=jnp.int32)).reshape(1, 2 * wp)
    lp = {"eva_phi": phi, "eva_mu": mu}
    closing = pd._EvaClose(jnp.ones((1,), bool), jnp.zeros((1,), jnp.int32))

    def closes(reps, kp, vp):
        def body(i, pools):
            return tuple(pd._eva_close(lp, *pools, 0, tab, closing, cfg,
                                       not tiny, tiny))
        return jax.lax.fori_loop(0, reps, body, (kp, vp))

    timed = jax.jit(closes, static_argnums=0, donate_argnums=(1, 2))
    kp, vp = jax.block_until_ready(timed(args.reps, kp, vp))
    best = float("inf")
    for _ in range(args.rounds):
        t = time.perf_counter()
        kp, vp = jax.block_until_ready(timed(args.reps, kp, vp))
        best = min(best, time.perf_counter() - t)
    us = best / args.reps * 1e6
    print(json.dumps({"point": "close", "form": "gather + kernel + scatter",
                      "us_per_call": us, "moved_mb": moved / 1e6,
                      "roof_pct": 100 * moved / peak / (us * 1e-6), **note}),
          flush=True)

    lanes = 2 if tiny else args.lanes
    per = w // c // page                       # summary pages a closed window
    most = max(int(x) for x in args.windows.split(","))
    cols = most * per + w // page
    pool = rand((2, 1 + lanes * cols, page, h, d))  # a layer an iteration
    table = (1 + jnp.arange(lanes * cols, dtype=jnp.int32)).reshape(lanes, cols)
    q = rand((lanes, 1, h, d))
    for closed in (int(x) for x in args.windows.split(",")):
        rows = closed * (w // c) + int(args.fill * w)
        off = jnp.full((lanes,), rows - 1, jnp.int32)
        fn = lambda i, acc, q, kp, vp: paged_flash_attention(  # noqa: E731
            q, kp, vp, i % 2, table, off, interpret=tiny)
        us = best_us(fn, (q, pool, pool), args.reps, args.rounds)
        moved = lanes * rows * row_bytes(kv_heads=h, head_dim=d)
        print(json.dumps({"point": "attention", "lanes": lanes,
                          "closed_windows": closed, "rows_a_lane": rows,
                          "us_per_call": us, "moved_mb": moved / 1e6,
                          "roof_pct": 100 * moved / peak / (us * 1e-6), **note}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
